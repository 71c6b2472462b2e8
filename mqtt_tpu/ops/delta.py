"""Delta-staged device matcher: serve from a frozen CSR snapshot while the
trie churns, stay bit-identical, rebuild in the background.

The plain :class:`~mqtt_tpu.ops.matcher.TpuMatcher` recompiles the whole CSR
index whenever the trie version moves — a full rebuild is seconds at 1M
subscriptions, which no live broker can afford on every SUBSCRIBE. The
reference never has this problem because its walk reads the live trie under
a mutex (topics.go:593-628); the device index trades that for snapshot
semantics, so this module supplies the staleness story (SURVEY.md §7
stage 5, hard part #2):

- The device keeps serving the last compiled snapshot.
- Every trie mutation (via ``TopicsIndex.add_observer``) records the mutated
  filter in a host-side *delta overlay*: an append log plus a mini-trie of
  just the mutated filters. Client/shared mutations are recorded as client
  subscriptions and inline mutations as inline subscriptions, so the
  overlay applies the same $-topic exclusion rules [MQTT-4.7.1-1/2] as the
  real walk (an inline delta on ``#`` must flag ``$SYS/...`` topics even
  though a client delta on ``#`` must not).
- Per matched topic, the mini-trie answers "could any mutation since the
  snapshot affect this topic's subscriber set?" — a topic that matches no
  delta filter has, by construction, an identical subscriber set in the
  snapshot and the live trie, so the device result is served; affected
  topics re-walk the live host trie. Results are therefore bit-identical to
  ``TopicsIndex.subscribers`` at every instant, at any rebuild cadence.
- A background thread recompiles the CSR when the overlay grows past
  ``rebuild_after`` filters (or on demand via :meth:`flush`); the overlay
  generation swaps atomically and carries over only the mutations that
  arrived while the walk ran.
- A bulk load (``TopicsIndex.bulk_load``: the durable restore,
  ``staging.bulk_register``) is ONE generation of the table. While one is
  open on the trie the background thread rebuilds nothing, since a table
  walked from a half-loaded trie is thrown away by the next chunk, and
  the generation is *whole-dirty*: every topic is affected, so every
  publish is walked on the host trie, which is exact, and the overlay
  keeps neither a mirror nor a list of a million loaded filters. A
  mutation that is not part of the load but arrives during it is covered
  the same way. The instant the outermost load closes, per-entry
  recording resumes and the thread is woken for the one full build; the
  generation that replaces a whole-dirty one is whole-dirty itself if a
  load's mutation raced its walk.

Because the overlay mini-trie IS a ``TopicsIndex``, its walk applies every
matching rule — including the parent-inline quirk (topics.go:615) — so the
affected-check is exact: a topic is routed to the host walk iff some
recorded mutation can actually reach it.
"""

from __future__ import annotations

import logging
import threading
import time
from typing import Optional

from ..packets import Subscription
from ..topics import InlineSubscription, Mutation, Subscribers, TopicsIndex
from .matcher import TpuMatcher

_DELTA_CLIENT = "\x00delta"  # mini-trie marker client; never a real client id
_log = logging.getLogger("mqtt_tpu.ops.delta")


def _noop_handler(*_a) -> None:  # pragma: no cover - marker, never invoked
    pass


class _Snapshot(TpuMatcher):
    """A TpuMatcher that never self-rebuilds: the delta overlay makes
    serving a stale snapshot safe, so staleness is frozen off."""

    @property
    def stale(self) -> bool:  # noqa: D401 - see class docstring
        return False


def _sharded_snapshot_cls():
    """The mesh-sharded analog of _Snapshot (imported lazily: mqtt_tpu.ops
    must not pull jax.sharding machinery unless a mesh is actually used)."""
    from ..parallel.sharded import ShardedTpuMatcher

    class _ShardedSnapshot(ShardedTpuMatcher):
        @property
        def stale(self) -> bool:
            return False

    return _ShardedSnapshot


class _Gen:
    """One snapshot generation: the compiled device index plus the overlay
    of filters mutated since its build started. ``held`` counts the
    mutations taken in while a bulk load was open on the trie: they are
    in no list and no mini-trie, and above 0 the generation is
    whole-dirty (every topic affected) until a full build replaces it."""

    __slots__ = ("snap", "delta_trie", "deltas", "seen", "held")

    def __init__(
        self, snap: _Snapshot, deltas: list[tuple[str, str]], held: int = 0
    ) -> None:
        self.snap = snap
        self.delta_trie = TopicsIndex()
        self.deltas: list[tuple[str, str]] = []
        self.seen: set[tuple[str, str]] = set()
        self.held = held
        for f, kind in deltas:
            self.record(f, kind)

    def record(self, filter: str, kind: str) -> None:
        key = (filter, kind)
        self.deltas.append(key)
        if key in self.seen:
            return
        self.seen.add(key)
        if filter:
            if kind == "inline":
                # inline markers follow inline gather rules (no $-exclusion)
                self.delta_trie.inline_subscribe(
                    InlineSubscription(filter=filter, identifier=1, handler=_noop_handler)
                )
            else:
                self.delta_trie.subscribe(_DELTA_CLIENT, Subscription(filter=filter))

    @property
    def pending(self) -> int:
        """Mutations since the snapshot, recorded or held."""
        return len(self.deltas) + self.held

    def affected(self, topic: str) -> bool:
        """True when some mutation since the snapshot may change ``topic``'s
        subscriber set."""
        if self.held:
            return True
        if not self.deltas:
            return False
        s = self.delta_trie.subscribers(topic)
        return bool(s.subscriptions or s.shared or s.inline_subscriptions)

    def affected_batch(self, topics: list[str]) -> list[int]:
        """Indices of topics the overlay may affect. The batch form lets
        the resolver skip the per-topic predicate loop entirely when no
        mutations are pending — the common case for a broker whose
        subscriptions arrive at connect time."""
        if self.held:
            return [i for i, t in enumerate(topics) if t]
        if not self.deltas:
            return []
        affected = self.affected
        return [i for i, t in enumerate(topics) if t and affected(t)]


class DeltaMatcher:
    """Drop-in for ``TopicsIndex.subscribers`` that serves device matches
    from a snapshot + host delta overlay and rebuilds off the hot path.

    A bulk load open on the trie (``TopicsIndex.bulk_depth`` above 0)
    holds the background rebuilds off: neither the ``rebuild_after``
    threshold nor the interval tick builds from a half-loaded trie
    (``stats.rebuilds_held`` counts the wake-ups put off), the overlay
    goes whole-dirty instead of recording each loaded filter, and the
    close of the outermost load (``stats.bulk_loads``) wakes the thread
    for one full build; ``bulk_build_seconds`` is how long the newest
    such build took. Nothing selects this: the trie says a load is open.
    An explicit :meth:`flush` builds synchronously whenever it is called.

    Parameters
    ----------
    rebuild_after:
        Overlay size (mutation events) that triggers an immediate background
        recompile. The overlay stays correct at any size — this only tunes
        how much traffic takes the slower host path.
    rebuild_interval:
        The background thread additionally folds a NON-empty overlay every
        this many seconds, so a quiet broker (e.g. all subscribes at connect
        time, publishes after) drains its overlay instead of serving the
        host path forever below the count threshold.
    background:
        When True (default), rebuilds run on a daemon thread; when False,
        call :meth:`flush` to recompile synchronously (tests, benchmarks).
    mesh:
        When given, the snapshot is a mesh-sharded matcher
        (``mqtt_tpu.parallel.ShardedTpuMatcher``) whose incremental rebuild
        recompiles only the shards touched since the last fold — the same
        overlay correctness story at per-shard rebuild cost.
    """

    def __init__(
        self,
        topics: TopicsIndex,
        max_levels: int = 8,
        out_slots: int = 64,
        rebuild_after: int = 1024,
        rebuild_interval: float = 1.0,
        background: bool = True,
        mesh=None,
        window: int = 16,
        compact: bool = True,
        compact_capacity: int = 0,
        hits_estimate: float = 2.0,
        lazy: bool = True,
    ) -> None:
        self.topics = topics
        self.max_levels = max_levels
        self.out_slots = out_slots
        self.window = window
        self.rebuild_after = rebuild_after
        self.rebuild_interval = rebuild_interval
        self.background = background
        self._lock = threading.Lock()  # guards generation swap + delta append
        self._rebuild_lock = threading.Lock()  # one rebuild at a time
        self._wake = threading.Event()
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        # background rebuilds that raised (logged and retried; a smoke
        # that must not pass on a degraded matcher reads this)
        self.rebuild_errors = 0
        # wall seconds of the newest build that replaced a whole-dirty
        # generation, i.e. the one build a bulk load ends in
        self.bulk_build_seconds = 0.0
        # ONE snapshot matcher reused across generations: both matcher kinds
        # swap their compiled state atomically, and the sharded one folds
        # deltas incrementally (per-shard) instead of recompiling the world
        if mesh is not None:
            snap = _sharded_snapshot_cls()(
                topics,
                mesh=mesh,
                max_levels=max_levels,
                out_slots=out_slots,
                window=window,
                compact=compact,
                compact_capacity=compact_capacity,
                hits_estimate=hits_estimate,
                lazy=lazy,
            )
        else:
            snap = _Snapshot(
                topics,
                max_levels=max_levels,
                out_slots=out_slots,
                window=window,
                # background rebuilds must not starve the serving thread's
                # match latency for the build duration (churn p99)
                cooperative=background,
                compact=compact,
                compact_capacity=compact_capacity,
                hits_estimate=hits_estimate,
                lazy=lazy,
            )
        snap.rebuild()
        self._snap = snap
        self._gen = _Gen(snap, [])
        topics.add_observer(self._on_mutation, self._on_bulk_end)
        if background:
            self._thread = threading.Thread(
                target=self._rebuild_loop, name="mqtt-tpu-csr-rebuild", daemon=True
            )
            self._thread.start()

    @property
    def stats(self):
        """The underlying matcher's observability counters."""
        return self._snap.stats

    # -- delta stream --------------------------------------------------------

    def _on_mutation(self, m: Mutation) -> None:
        # called under the trie lock, which bulk_depth moves under too
        loading = self.topics.bulk_depth > 0
        with self._lock:
            gen = self._gen
            if loading:
                gen.held += 1
            else:
                gen.record(m.filter, m.kind)
            pending = gen.pending
        if pending >= self.rebuild_after:
            if not loading:
                self._wake.set()
            elif pending == self.rebuild_after:
                self._snap.stats.rebuilds_held += 1

    def _on_bulk_end(self) -> None:
        """The outermost bulk load closed (under the trie lock): wake the
        rebuild thread for the one build, with no caller's flush()."""
        self._snap.stats.bulk_loads += 1
        self._wake.set()

    @property
    def pending_deltas(self) -> int:
        with self._lock:
            return self._gen.pending

    # -- rebuild -------------------------------------------------------------

    def _rebuild_snapshot(self, filters=None) -> None:
        """Fold the live trie into the snapshot without holding its lock;
        concurrent structural mutations can tear the walk (RuntimeError from
        a mutated dict iteration, KeyError from a node inserted mid-walk),
        in which case retry — every mutation racing the walk is in the delta
        overlay, so a successful walk is always safe to serve.

        When the pending mutations' filter set is known, the single-device
        snapshot first attempts an incremental fold (TpuMatcher.fold):
        per-bucket in-place edits plus a ~KB device scatter instead of a
        full rebuild + table upload — the difference between multi-second
        and sub-ms p99 under churn on a slow host<->device link."""
        if filters is not None and hasattr(self._snap, "fold"):
            try:
                if self._snap.fold(filters):
                    return
            except (RuntimeError, KeyError):
                pass  # torn reads: fall through to the retried full path
        if getattr(self._snap, "handles_tears", False):
            # the sharded snapshot retries tears (and quiesces) internally;
            # its rebuild takes its rebuild mutex BEFORE the trie lock, so
            # wrapping it in `with self.topics._lock` here would invert
            # that order and deadlock against a concurrent rebuild
            self._snap.rebuild()
            return
        for _ in range(8):
            try:
                self._snap.rebuild()
                return
            except (RuntimeError, KeyError):
                continue
        with self.topics._lock:  # mutation storm: build quiesced
            self._snap.rebuild()

    def _rebuild_once(self) -> None:
        with self._rebuild_lock:
            with self._lock:
                old = self._gen
                k = len(old.deltas)
                held = old.held
            if k == 0 and held == 0:
                return
            t0 = time.perf_counter()
            # a whole-dirty generation has no filter set to offer fold():
            # what ends a bulk load is a full rebuild
            self._rebuild_snapshot(
                filters=None if held else {f for f, _ in old.deltas[:k]}
            )
            with self._lock:
                # mutations that raced the walk (recorded after index k,
                # or held after the count read above) might be missing
                # from the new snapshot: carry them over
                self._gen = _Gen(self._snap, old.deltas[k:], old.held - held)
            if held:
                self.bulk_build_seconds = time.perf_counter() - t0

    def flush(self) -> None:
        """Synchronously fold all pending deltas into a fresh snapshot."""
        self._rebuild_once()

    def _rebuild_loop(self) -> None:
        while not self._stop.is_set():
            # wake on overflow OR on the interval tick, so a quiet overlay
            # still drains (count threshold alone could starve forever)
            self._wake.wait(timeout=self.rebuild_interval)
            self._wake.clear()
            if self._stop.is_set():
                return
            if self.topics.bulk_depth > 0:
                # a load is open: whatever this walked would be replaced
                # whole. Its close sets _wake (after the depth is back to
                # 0 and after the clear above, so the wake is not lost)
                if self._gen.pending:
                    self._snap.stats.rebuilds_held += 1
                continue
            try:
                self._rebuild_once()
            except Exception:
                # never let the rebuild thread die: a degraded matcher keeps
                # serving (host path), a dead one degrades forever
                self.rebuild_errors += 1
                _log.exception("background CSR rebuild failed; will retry")
                self._stop.wait(1.0)
                self._wake.set()

    def close(self) -> None:
        self.topics.remove_observer(self._on_mutation, self._on_bulk_end)
        if hasattr(self._snap, "close"):
            self._snap.close()  # detach the sharded snapshot's own observer
        self._stop.set()
        self._wake.set()
        if self._thread is not None:
            self._thread.join(timeout=5)

    # -- matching ------------------------------------------------------------

    def match_topics_async(self, topics: list[str], profile=None):
        """Issue one batch; the returned resolver yields the results.
        The generation (snapshot + overlay) is captured at issue time; the
        generation object itself is the route-to-host authority (it
        exposes both the per-topic ``affected`` predicate and the batch
        form the C materializer prefers). ``profile`` is the caller's
        optional per-batch BatchProfile (mqtt_tpu.tracing), forwarded to
        the snapshot matcher."""
        gen = self._gen  # atomic read: one generation per call
        return gen.snap.match_topics_async(topics, route_to_host=gen, profile=profile)

    def match_topics(self, topics: list[str]) -> list[Subscribers]:
        """Match a batch of topics, bit-identical to the live host trie."""
        return self.match_topics_async(topics)()

    def subscribers(self, topic: str) -> Subscribers:
        """Drop-in for ``TopicsIndex.subscribers`` (batch of one)."""
        return self.match_topics([topic])[0]
