"""Subscription-sharded matching over a 2D device mesh.

Mesh axes:

- ``batch`` — data parallelism over the PUBLISH topic batch
- ``subs``  — model-style parallelism over the subscription set: each device
  along this axis holds the flat-hash index (ops/flat.py) of its
  subscription shard

One jitted step matches every (topic-shard, sub-shard) tile locally and
``all_gather``s the per-shard match lists over the ``subs`` axis (ICI), so
every batch row ends with the full union of sub ids. The host maps local
sub ids through per-shard tables and merges — bit-identical to the
single-device matcher, which is bit-identical to the host trie.

Shard assignment is a stable hash of (client, filter) — NOT round-robin
over enumeration order — so one subscription mutation touches exactly one
shard. The matcher keeps a per-shard replica ``TopicsIndex`` maintained
from the trie's mutation stream (``TopicsIndex.add_observer``), marks the
owning shard dirty, and an incremental ``rebuild()`` recompiles only dirty
shards: cost per mutation is bounded by one shard (~1/S of the index)
instead of the full index (reference mutation semantics: topics.go:479-522).
"""

from __future__ import annotations

import logging
import threading
import time
import zlib
from typing import Callable, Optional

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..telemetry import FILL_BOUNDS, Histogram
from ..topics import Mutation, Subscribers, TopicsIndex
from ..ops.flat import (
    KIND_CLIENT,
    KIND_INLINE,
    KIND_SHARED,
    SubEntry,
    _bucket,
    _node_snap,
    _pad_to,
    _walk_terminals,
    build_flat_index,
    flat_match_core,
)
from ..ops.backend import ensure_compile_cache
from ..ops.devicestats import KernelWatch
from ..ops.hashing import tokenize_topics
from ..ops.matcher import (
    MatcherStats,
    _accel,
    expand_sids,
    fold_hits_ewma,
    materialize_compact_pairs,
    pick_compact_capacity,
)

_log = logging.getLogger("mqtt_tpu.parallel")


def make_mesh(devices=None, batch_axis: Optional[int] = None) -> Mesh:
    """A 2D (batch, subs) mesh over the given (default: all) devices."""
    devices = list(devices if devices is not None else jax.devices())
    n = len(devices)
    if batch_axis is None:
        batch_axis = 2 if n % 2 == 0 and n > 1 else 1
    subs_axis = n // batch_axis
    grid = np.array(devices[: batch_axis * subs_axis]).reshape(batch_axis, subs_axis)
    return Mesh(grid, ("batch", "subs"))


def _tile_compact_core(out, totals, overflow, *, cap_local):
    """Compact one batch-tile's gathered result ON DEVICE (ROADMAP item
    1 feeding item 2's cheap all-gather): the device's local
    ``[S, b_local, K]`` -1-padded slot view becomes a topic-major
    ``(shard, sid)`` pair stream sized for the hits that exist, so the
    D2H moves ~``hits x 8`` bytes instead of ``S x B x K x 4``.

    Runs INSIDE a shard_map over the ``batch`` mesh axis (the gathered
    arrays come from a ``check_vma=False`` shard_map, whose claimed
    replication plain jitted jnp code must not trust — the same reason
    the match step itself is explicit SPMD). Per-tile output row:
    ``[2 + 2*b_local + 2*cap_local]`` = ``(tile_hits, tile_overflow |
    totals[b_local] | overflow[b_local] | pair_shard[cap_local] |
    pair_sid[cap_local])``. Per-segment counts are clamped to ``K`` —
    rows past the slot window are overflow-flagged by the kernel and
    host-routed, so their surplus never reaches the pair stream."""
    import jax.numpy as jnp

    from ..ops.flat import _segment_of_slot

    S, bl, K = out.shape
    out_t = jnp.transpose(out, (1, 0, 2)).reshape(bl * S, K)
    t_flat = jnp.minimum(jnp.transpose(totals, (1, 0)).reshape(bl * S), K)
    cum = jnp.cumsum(t_flat)
    offs = cum - t_flat
    n_hits = cum[-1]
    k = jnp.arange(cap_local, dtype=jnp.int32)
    seg_c = _segment_of_slot(t_flat, offs, cap_local)
    slot = jnp.minimum(k - offs[seg_c].astype(jnp.int32), K - 1)
    sid = out_t[seg_c, slot]
    shard = seg_c % S
    valid = k < n_hits
    per_topic = jnp.minimum(totals, K).sum(axis=0).astype(jnp.int32)
    ovf_topic = overflow.any(axis=0).astype(jnp.int32)
    header = jnp.stack(
        [n_hits.astype(jnp.int32), (n_hits > cap_local).astype(jnp.int32)]
    )
    vec = jnp.concatenate(
        [
            header,
            per_topic,
            ovf_topic,
            jnp.where(valid, shard, -1),
            jnp.where(valid, sid, -1),
        ]
    )
    return vec[None, :]


def shard_of(kind, client: str, filter: str, identifier: int, n_shards: int) -> int:
    """Stable shard assignment: a deterministic hash of the subscription's
    identity, independent of enumeration order or churn history — so the
    same subscription always lands on the same shard and a mutation dirties
    exactly one shard."""
    if kind in (KIND_INLINE, "inline"):
        key = f"\x00inline\x00{identifier}\x00{filter}"
    else:
        key = f"{client}\x00{filter}"
    return zlib.crc32(key.encode("utf-8", "surrogatepass")) % n_shards


class ShardedTpuMatcher:
    """Shards a TopicsIndex's subscriptions across the ``subs`` mesh axis
    and matches topic batches with one SPMD step.

    With ``incremental=True`` (default) the matcher subscribes to the
    trie's mutation stream and ``rebuild()`` recompiles only the shards
    whose subscriptions changed; call :meth:`close` to detach the observer.
    """

    # rebuild() retries torn walks and quiesces internally — callers (the
    # delta overlay) must NOT wrap it in `with topics._lock`, which would
    # invert this class's rebuild-mutex -> trie-lock order and deadlock
    handles_tears = True

    def __init__(
        self,
        topics: TopicsIndex,
        mesh: Optional[Mesh] = None,
        max_levels: int = 8,
        out_slots: int = 64,
        window: int = 16,
        incremental: bool = True,
        compact: bool = True,
        compact_capacity: int = 0,
        hits_estimate: float = 2.0,
        lazy: bool = False,
    ) -> None:
        self.topics = topics
        self.mesh = mesh or make_mesh()
        self.max_levels = max_levels
        self.out_slots = out_slots
        self.window = window
        self.n_shards = self.mesh.shape["subs"]
        self.n_batch = self.mesh.shape["batch"]
        self.incremental = incremental
        # device-resident hit compaction of the gathered result (see
        # _gather_compact_core); same knob contract as TpuMatcher
        self.compact = compact
        self.compact_capacity = max(0, compact_capacity)
        # lazy SubscribersView results over the stitched per-tile pair
        # stream (ISSUE 15 satellite closing the ISSUE 13 residual):
        # resolve_compact_views consumes the sharded (sid, shard) form
        # natively — per-hit objects are built only when fan-out asks.
        # The eager expansion stays as the differential oracle, and
        # without the C module laziness silently degrades to eager
        # (materialize_compact_pairs' contract).
        self.lazy = lazy
        self._hits_ewma = max(1.0, float(hits_estimate))
        # sticky per-batch-bucket capacities (TpuMatcher contract: grow
        # immediately, shrink only at 4x oversize — every distinct
        # capacity is one XLA executable)
        self._caps: dict[int, int] = {}
        self.stats = MatcherStats()
        # device pipeline profiler (mqtt_tpu.tracing.DeviceProfiler) or
        # None; same seam as TpuMatcher.profiler (ops/matcher.py) — the
        # SPMD step's dispatch and D2H windows feed duty-cycle/overlap/
        # idle-gap accounting when the server attaches one
        self.profiler = None
        # one (arrays, tables, salt, step) tuple swapped atomically so a
        # concurrent match never mixes generations
        self._compiled: Optional[tuple] = None
        self._built_version = -1
        # per-shard replica tries + their last compiled flat indexes +
        # dirty flags; guarded by _state_lock (held briefly — the observer
        # runs under the main trie's lock, so installs must never block)
        self._state_lock = threading.Lock()
        # serializes whole rebuilds: without it, a concurrent rebuild can
        # observe the storm path's intermediate state (fresh replicas,
        # cleared dirty flags, old compiled arrays) and stamp the stale
        # snapshot as current via the empty-dirty early return
        self._rebuild_mutex = threading.Lock()
        self._replicas: Optional[list[TopicsIndex]] = None
        self._flats: Optional[list] = None
        self._dirty = [False] * self.n_shards
        self._salt = 0
        self._step: Optional[Callable] = None
        # jitted per-tile compaction steps, keyed on cap_local (each
        # capacity is one executable; jax re-traces per input shape)
        self._compact_steps: dict[int, Callable] = {}
        # per-shard compile-time histogram SHARDS (mqtt_tpu.telemetry):
        # the thread compiling shard s records into shard s's local
        # histogram — no cross-thread write sharing — and the scrape
        # merges them on demand (merged_shard_compile), the merge()-at-
        # scrape pattern the telemetry plane's Histogram documents
        self.shard_compile_hists = [Histogram() for _ in range(self.n_shards)]
        # per-tile imbalance telemetry (ISSUE 18): cumulative hit counts
        # and per-batch fill histograms, one per batch tile, folded from
        # each resolved compact batch under _tile_lock (arithmetic only).
        # device_skew_ratio() = max/mean over tile_hits — the live gauge
        # the multi-chip frontier's "near-linear scaling" claim reads.
        self._tile_lock = threading.Lock()
        self._tile_hits = np.zeros(self.n_batch, dtype=np.int64)
        self._tile_batches = 0
        self.tile_fill_hists = [
            Histogram(bounds=FILL_BOUNDS) for _ in range(self.n_batch)
        ]
        # mesh device ids, dispatch-stamped onto each BatchProfile so the
        # profiler's per-device windows attribute sharded batches
        self._device_ids = tuple(
            int(getattr(d, "id", i))
            for i, d in enumerate(self.mesh.devices.flat)
        )
        if incremental:
            topics.add_observer(self._on_mutation)

    def tile_hit_counts(self) -> np.ndarray:
        """Cumulative per-batch-tile hit counts (a copy)."""
        with self._tile_lock:
            return self._tile_hits.copy()

    def device_skew_ratio(self) -> float:
        """max/mean per-tile cumulative hits: 1.0 = balanced mesh,
        n_batch = one hot tile, 0.0 = no traffic yet."""
        with self._tile_lock:
            hits = self._tile_hits
            mean = float(hits.mean()) if hits.size else 0.0
            if mean <= 0.0:
                return 0.0
            return float(hits.max()) / mean

    def _fold_tile_hits(self, tile_hits: np.ndarray, cap_local: int) -> None:
        """Fold one resolved batch's per-tile hit counts into the skew
        accounting (called from resolve closures, any thread)."""
        n = min(len(tile_hits), self.n_batch)
        with self._tile_lock:
            self._tile_hits[:n] += tile_hits[:n].astype(np.int64)
            self._tile_batches += 1
            if cap_local > 0:
                for t in range(n):
                    self.tile_fill_hists[t].observe(
                        float(tile_hits[t]) / cap_local
                    )

    def close(self) -> None:
        """Detach from the trie's mutation stream."""
        self.topics.remove_observer(self._on_mutation)

    # -- delta stream --------------------------------------------------------

    def _on_mutation(self, m: Mutation) -> None:
        """Apply one trie mutation to the owning shard's replica and mark it
        dirty. Called under the main trie's lock — must stay fast and must
        never raise into the broker's subscribe path."""
        with self._state_lock:
            reps = self._replicas
            if reps is None:
                return  # first full build will capture current state
            s = shard_of(m.kind, m.client, m.filter, m.identifier, self.n_shards)
            try:
                rep = reps[s]
                if m.kind == "inline":
                    if m.op == "add":
                        rep.inline_subscribe(m.subscription)
                    else:
                        rep.inline_unsubscribe(m.identifier, m.filter)
                else:
                    if m.op == "add":
                        rep.subscribe(m.client, m.subscription)
                    else:
                        rep.unsubscribe(m.filter, m.client)
                self._dirty[s] = True
            except Exception:
                _log.exception("shard replica update failed; forcing full rebuild")
                self._replicas = None

    # -- build -------------------------------------------------------------

    def rebuild(self) -> None:
        """Bring the compiled index up to date.

        Full path (first build, or after a replica fault): walk the live
        trie, partition by stable hash into fresh replicas, compile all
        shards. Incremental path: recompile only dirty shards' replicas and
        restack — cost bounded by the dirty shards, not the index.

        The observer's fault path can null the replicas mid-compile; each
        attempt would then fold nothing, so retry a bounded number of
        times instead of recursing unboundedly under a persistent fault."""
        t0 = time.perf_counter()
        with self._rebuild_mutex:
            # the except runs INSIDE the mutex: re-marking dirty after
            # release would leave a gap where a concurrent rebuild sees
            # empty dirty flags and stamps the stale snapshot as current
            try:
                for attempt in range(4):
                    if self._replicas is None or not self.incremental:
                        done = self._full_rebuild()
                    else:
                        done = self._incremental_rebuild()
                    if done:
                        break
                else:
                    raise RuntimeError(
                        "rebuild could not complete: persistent replica faults"
                    )
            except BaseException:
                # exception safety: a rebuild that dies after clearing dirty
                # flags (e.g. device_put fault in _assemble) must not let the
                # next rebuild's empty-dirty early-return pass off the stale
                # snapshot as current — over-mark everything dirty instead
                with self._state_lock:
                    self._dirty = [True] * self.n_shards
                raise
        self.stats.rebuilds += 1
        self.stats.note_rebuild(time.perf_counter() - t0)

    def _partition_live(self) -> list[TopicsIndex]:
        """Walk the live trie and split its subscriptions into fresh
        per-shard replicas. Concurrent structural mutations can tear the
        walk (RuntimeError/KeyError from dict iteration) — callers retry."""
        replicas = [TopicsIndex() for _ in range(self.n_shards)]
        for _path, node in _walk_terminals(self.topics):
            cli, shr, inl = _node_snap(node)
            for client, sub in cli:
                s = shard_of(KIND_CLIENT, client, sub.filter, 0, self.n_shards)
                replicas[s].subscribe(client, sub)
            for client, sub in shr:
                s = shard_of(KIND_SHARED, client, sub.filter, 0, self.n_shards)
                replicas[s].subscribe(client, sub)
            for isub in inl:
                s = shard_of(
                    KIND_INLINE, "", isub.filter, isub.identifier, self.n_shards
                )
                replicas[s].inline_subscribe(isub)
        return replicas

    def _full_rebuild(self) -> bool:
        for attempt in range(8):
            v0 = self.topics.version
            try:
                replicas = self._partition_live()
            except (RuntimeError, KeyError):
                continue  # concurrent mutation tore the walk; retry
            flats = self._compile_all(replicas)
            if self.topics.version != v0:
                continue  # doomed: skip the H2D transfer, retry the walk
            # device placement happens OUTSIDE _state_lock: the observer
            # runs under the broker trie's lock and blocks on _state_lock,
            # so holding it across an H2D transfer would stall every
            # subscribe for the transfer time
            compiled = self._assemble(flats)
            with self._state_lock:
                if self.topics.version == v0:
                    self._replicas = replicas
                    self._flats = flats
                    self._dirty = [False] * self.n_shards
                    self._salt = flats[0].salt
                    self._compiled = compiled
                    self._built_version = v0
                    return True
            # a mutation landed while we walked: the fresh replicas may miss
            # it (the observer was still feeding the OLD replicas) — retry
        # mutation storm: quiesce the trie ONLY long enough to walk it and
        # swap fresh replicas in (pure host work, no device transfers) —
        # subscribes resume while we compile; every mutation from the swap
        # onward feeds the new replicas and marks its shard dirty, and
        # _built_version = v0 keeps `stale` true until they are folded
        with self.topics._lock:
            v0 = self.topics.version
            replicas = self._partition_live()
            with self._state_lock:
                self._replicas = replicas
                self._dirty = [False] * self.n_shards
        flats = self._compile_all(replicas, retry_tears=True)
        compiled = self._assemble(flats)
        with self._state_lock:
            fault = self._replicas is not replicas
            if not fault:
                self._flats = flats
                self._salt = flats[0].salt
                self._compiled = compiled
                self._built_version = v0
        # on fault the observer nulled the replicas mid-compile; returning
        # success would report a rebuild that folded nothing (DeltaMatcher
        # would drop its overlay) — the caller retries, boundedly
        return not fault

    def _incremental_rebuild(self) -> bool:
        # read the version under the trie lock: the trie bumps it BEFORE
        # notifying observers, so a bare read could adopt a version whose
        # mutation hasn't marked its shard dirty yet — stamping that
        # version as built would hide the unfolded shard from `stale`.
        # Holding the trie lock waits out any in-flight notify.
        with self.topics._lock:
            version = self.topics.version
        with self._state_lock:
            # snapshot under the lock: the observer's exception path sets
            # _replicas = None concurrently, and reading a torn
            # replicas/flats/dirty trio would crash the rebuild thread with
            # an exception type no caller retries (TypeError)
            replicas = self._replicas
            if replicas is None or self._flats is None:
                replicas = None  # fall through to a full rebuild below
            else:
                dirty = [s for s in range(self.n_shards) if self._dirty[s]]
                # clear BEFORE compiling: a mutation racing the compile
                # re-marks the shard, so it is recompiled next round even
                # if this walk already included it
                for s in dirty:
                    self._dirty[s] = False
                flats = list(self._flats)
                if not dirty and self._compiled is not None:
                    # nothing to fold: stamp INSIDE the lock — outside it, a
                    # mutation between the dirty check and the stamp could
                    # publish a version whose shard was never folded
                    self._built_version = version
                    return True
        if replicas is None:
            return self._full_rebuild()
        for s in dirty:
            # compile at the generation's bucket count up front: defaulting
            # to the minimum would make _unify recompile the shard again
            flats[s] = self._compile_shard(
                s, replicas, min_buckets=flats[s].table.shape[0]
            )
        flats = self._unify(flats, replicas)
        compiled = self._assemble(flats)
        with self._state_lock:
            fault = self._replicas is not replicas
            if not fault:
                self._flats = flats
                self._salt = flats[0].salt  # keep in sync: a bump here must
                # not force the next incremental round to recompile the world
                self._compiled = compiled
                self._built_version = version
        # on fault: see _full_rebuild — the caller retries, boundedly
        return not fault

    def merged_shard_compile(self) -> Histogram:
        """One merged snapshot of the per-shard compile-time histogram
        shards (scrape-time callback for the telemetry registry)."""
        merged = Histogram()
        for h in self.shard_compile_hists:
            merged.merge(h)
        return merged

    def _compile_shard(
        self,
        s: int,
        replicas,
        salt: Optional[int] = None,
        min_buckets: int = 1024,
        retry_tears: bool = True,
    ):
        t0 = time.perf_counter()
        try:
            return self._compile_shard_inner(
                s, replicas, salt, min_buckets, retry_tears
            )
        finally:
            # shard-local: only the thread compiling shard s writes here
            self.shard_compile_hists[s].observe(time.perf_counter() - t0)

    def _compile_shard_inner(
        self,
        s: int,
        replicas,
        salt: Optional[int] = None,
        min_buckets: int = 1024,
        retry_tears: bool = True,
    ):
        rep = replicas[s]
        salt = self._salt if salt is None else salt
        if retry_tears:
            for _ in range(8):
                try:
                    return build_flat_index(
                        rep,
                        max_levels=self.max_levels,
                        salt=salt,
                        window=self.window,
                        min_buckets=min_buckets,
                    )
                except (RuntimeError, KeyError):
                    continue  # replica mutated mid-walk; retry
            with rep._lock:  # mutation storm on this shard: build quiesced
                return build_flat_index(
                    rep,
                    max_levels=self.max_levels,
                    salt=salt,
                    window=self.window,
                    min_buckets=min_buckets,
                )
        # fresh, unpublished replicas can't tear: no retry wrapper
        return build_flat_index(
            rep,
            max_levels=self.max_levels,
            salt=salt,
            window=self.window,
            min_buckets=min_buckets,
        )

    def _compile_all(self, replicas: list[TopicsIndex], retry_tears: bool = False):
        """Compile every shard at a uniform salt and bucket count. With
        ``retry_tears`` the per-shard compile retries walks torn by
        concurrent replica mutations (live replicas); without it a tear
        propagates to the caller (fresh, unpublished replicas can't tear)."""

        def compile_one(s: int, salt: int, min_buckets: int = 1024):
            return self._compile_shard(
                s, replicas, salt=salt, min_buckets=min_buckets,
                retry_tears=retry_tears,
            )

        flats = [compile_one(s, self._salt) for s in range(len(replicas))]
        return self._unify(flats, replicas, compile_one)

    def _unify(self, flats, replicas, compile_one=None):
        """Recompile shards until all

        - agree on the hash salt (topics tokenize at ONE salt: serving
          mixed-salt shards would silently drop subscribers), and
        - agree on the bucket count (the stacked table is one array; each
          shard's ``slot = h1 & (S-1)`` must use the stacked S).
        """
        if compile_one is None:

            def compile_one(s, salt, min_buckets=1024):
                return self._compile_shard(s, replicas, salt=salt, min_buckets=min_buckets)

        for _ in range(8):
            salts = {f.salt for f in flats}
            sizes = {f.table.shape[0] for f in flats}
            if len(salts) == 1 and len(sizes) == 1:
                return flats
            salt = max(salts)
            S = max(sizes)
            flats = [
                f
                if f.salt == salt and f.table.shape[0] == S
                else compile_one(s, salt, min_buckets=S)
                for s, f in enumerate(flats)
            ]
        if len({(f.salt, f.table.shape[0]) for f in flats}) == 1:
            return flats
        raise RuntimeError("shard salt/size unification failed")

    def _assemble(self, flats) -> tuple:
        """Stack per-shard flat indexes into mesh-placed device arrays and
        return the compiled generation (the caller swaps it in under
        _state_lock — device placement itself must happen lock-free).
        Shapes are power-of-two bucketed so churn rebuilds reuse the jitted
        executable. Padding is inert: pad patterns have depth -1 (never
        active) and pad id slots sit beyond every entry's window."""

        def stack(get, fill=0, min_len=2):
            arrs = [np.asarray(get(f)) for f in flats]
            n = _bucket(max(min_len, max(len(a) for a in arrs)), minimum=min_len)
            return np.stack([_pad_to(a, n, fill) for a in arrs])

        # table bucket counts are unified by _unify; stack directly
        table = np.stack([f.table for f in flats])
        shard_sharding = NamedSharding(self.mesh, P("subs"))
        arrays = tuple(
            jax.device_put(np.asarray(a), shard_sharding)
            for a in (
                table,
                stack(lambda f: f.pat_kind, fill=np.uint32(0)),
                stack(lambda f: f.pat_depth, fill=np.int32(-1)),
                stack(lambda f: f.pat_mask, fill=np.uint32(0)),
            )
        )
        tables = [f.subs for f in flats]
        step = self._get_step()
        return (arrays, tables, flats[0].salt, step)

    def _get_step(self):
        """The jitted SPMD step (cached; jax re-traces per shape)."""
        if self._step is not None:
            return self._step
        ensure_compile_cache()
        mesh = self.mesh
        max_levels, out_slots = self.max_levels, self.out_slots

        def step_fn(
            table, pat_kind, pat_depth, pat_mask,
            tok1, tok2, lengths, is_dollar,
        ):
            # each device: its sub shard (leading dim 1) x its batch tile
            out, totals, overflow = flat_match_core(
                table[0], pat_kind[0], pat_depth[0], pat_mask[0],
                tok1, tok2, lengths, is_dollar,
                max_levels=max_levels, out_slots=out_slots,
            )
            # union across the subs axis rides ICI
            out_g = jax.lax.all_gather(out, "subs")  # [S, b_local, K]
            tot_g = jax.lax.all_gather(totals, "subs")  # [S, b_local]
            ovf_g = jax.lax.all_gather(overflow, "subs")
            return out_g, tot_g, ovf_g

        shard_spec = P("subs")
        batch_spec = P("batch")
        step = KernelWatch(
            "sharded_step",
            jax.jit(
                shard_map(
                    step_fn,
                    mesh=mesh,
                    in_specs=(shard_spec,) * 4 + (batch_spec,) * 4,
                    out_specs=(P(None, "batch", None), P(None, "batch"), P(None, "batch")),
                    check_vma=False,
                )
            ),
        )
        self._step = step
        return step

    def _get_compact_step(self, cap_local: int) -> Callable:
        """The jitted shard_map'd per-tile compaction for one local
        capacity (cached; jax re-traces per input shape)."""
        step = self._compact_steps.get(cap_local)
        if step is None:
            ensure_compile_cache()
            fn = partial(_tile_compact_core, cap_local=cap_local)
            # cap_local is baked into the traced fn, not a call arg: give
            # the watch a per-capacity kernel label so a capacity-churn
            # recompile (the PR 11 incident) attributes to its capacity
            step = KernelWatch(
                f"sharded_tile_compact_c{cap_local}",
                jax.jit(
                    shard_map(
                        fn,
                        mesh=self.mesh,
                        in_specs=(
                            P(None, "batch", None),
                            P(None, "batch"),
                            P(None, "batch"),
                        ),
                        out_specs=P("batch", None),
                        check_vma=False,
                    )
                ),
            )
            self._compact_steps[cap_local] = step
        return step

    @property
    def stale(self) -> bool:
        return self._compiled is None or self._built_version != self.topics.version

    # -- matching ----------------------------------------------------------

    def match_topics_async(self, topics: list[str], route_to_host=None, profile=None):
        """Issue one SPMD match step and return a zero-arg resolver.

        Mirrors ``TpuMatcher.match_topics_async`` (ops/matcher.py): the
        step is dispatched asynchronously; the resolver performs the D2H
        sync plus host-side expansion and returns ``list[Subscribers]``.
        The delta overlay (ops/delta.py) relies on this API existing on
        every snapshot kind. ``profile`` is the caller's optional
        per-batch BatchProfile (mqtt_tpu.tracing), same contract as
        TpuMatcher."""
        if self._compiled is None or self.stale:
            self.rebuild()
        arrays, tables, salt, step = self._compiled
        prof = self.profiler
        rec = None
        if prof is not None:
            rec = profile if profile is not None else prof.open_batch()
            t_issue0 = time.perf_counter()
        b = len(topics)
        # pad ragged batches to a power-of-two bucket (one jitted executable
        # across the staging loop's window sizes), rounded up to a multiple
        # of the batch axis for even sharding
        target = _bucket(max(1, b), minimum=max(2, self.n_batch))
        target += (-target) % self.n_batch
        padded = topics + [""] * (target - b)
        tok1, tok2, lengths, is_dollar, len_overflow = tokenize_topics(
            padded, self.max_levels, salt
        )
        batch_sharding = NamedSharding(self.mesh, P("batch"))
        out_dev, totals_dev, overflow_dev = step(
            *arrays,
            *(
                jax.device_put(np.asarray(a), batch_sharding)
                for a in (tok1, tok2, lengths, is_dollar)
            ),
        )
        bp = len(padded)
        bl = bp // self.n_batch
        cap_local = 0
        compact_dev = None
        if self.compact:
            # compact the gathered result ON DEVICE before any transfer:
            # the [S, B, K] slot buffer collapses to per-tile topic-major
            # (shard, sid) pair streams sized for the hits that exist
            cap_local = max(
                16, self._compact_capacity_for(bp) // self.n_batch
            )
            compact_dev = self._get_compact_step(cap_local)(
                out_dev, totals_dev, overflow_dev
            )
            compact_dev.copy_to_host_async()
        if prof is not None:
            # device pipeline profiler: the SPMD issue leg ends here; every
            # mesh device participated in the step, so the per-device
            # windows (ISSUE 18) each get this batch's window
            rec.devices = self._device_ids
            prof.note_dispatch(rec, t_issue0, time.perf_counter())
        # accept both route forms (ops/matcher.py): a plain predicate or
        # the delta overlay object exposing .affected
        if route_to_host is not None and hasattr(route_to_host, "affected"):
            route_to_host = route_to_host.affected

        def resolve_full(t_sync0: float) -> list[Subscribers]:
            # brokerlint: ok=R15 the blessed resolve seam: one D2H per array after copy_to_host_async, [S, B, K]
            out = np.asarray(out_dev)
            # brokerlint: ok=R15 same resolve seam, the [B] overflow mask rides the batched readback
            overflow = np.asarray(overflow_dev).any(axis=0) | len_overflow
            self.stats.d2h_bytes += int(out.nbytes)
            if prof is not None:
                rec.d2h_bytes += int(out.nbytes)
                prof.note_resolve(rec, t_sync0, time.perf_counter())
            results = []
            stats = self.stats
            acc = _accel()  # once per batch, not per topic
            for i, topic in enumerate(topics):
                if not topic:
                    results.append(Subscribers())
                elif overflow[i] or (
                    route_to_host is not None and route_to_host(topic)
                ):
                    stats.host_fallbacks += 1
                    stats.overflows += int(overflow[i])
                    results.append(self.topics.subscribers(topic))
                else:
                    results.append(self._expand(tables, out[:, i, :], acc))
            return results

        if compact_dev is None:

            def resolve() -> list[Subscribers]:
                t_sync0 = time.perf_counter() if prof is not None else 0.0
                self.stats.batches += 1
                self.stats.topics += b
                return resolve_full(t_sync0)

            return resolve

        def resolve_compact() -> list[Subscribers]:
            t_sync0 = time.perf_counter() if prof is not None else 0.0
            # [n_batch, 2 + 2*bl + 2*cap_local]: one compacted row per
            # batch tile (shard_map over the batch axis)
            # brokerlint: ok=R15 the blessed resolve seam: ONE compacted-row D2H after copy_to_host_async
            rows = np.asarray(compact_dev)
            stats = self.stats
            stats.batches += 1
            stats.topics += b
            n_hits = int(rows[:, 0].sum())
            batch_ovf = bool(rows[:, 1].any())
            self._observe_hits(n_hits, b)
            # per-tile imbalance fold (ISSUE 18): every resolved batch —
            # including the overflow fallback, whose tile counts are
            # saturated-but-honest — feeds the skew gauge
            self._fold_tile_hits(np.asarray(rows[:, 0]), cap_local)
            if batch_ovf:
                # a tile outgrew its pair buffer: fall back to the full
                # gathered transfer for THIS batch only (the device
                # arrays are still resident — one extra sync, no
                # recompute)
                stats.compact_overflows += 1
                self._hits_ewma = max(self._hits_ewma, n_hits / max(1, b))
                # the compacted stream was synced too: both transfers
                # count (resolve_full adds the full gather's bytes)
                stats.d2h_bytes += int(rows.nbytes)
                if rec is not None:
                    rec.compact = True
                    rec.d2h_bytes = int(rows.nbytes)
                return resolve_full(t_sync0)
            stats.compact_batches += 1
            stats.d2h_bytes += int(rows.nbytes)
            if prof is not None:
                rec.d2h_bytes = int(rows.nbytes)
                rec.compact = True
                prof.note_resolve(rec, t_sync0, time.perf_counter())
            # stitch the per-tile streams back into one topic-major batch
            per_topic = rows[:, 2 : 2 + bl].reshape(bp)
            true_overflow = (
                rows[:, 2 + bl : 2 + 2 * bl].reshape(bp).astype(bool)
                | len_overflow
            )
            tile_hits = rows[:, 0]
            pair_shard = np.concatenate(
                [
                    rows[t, 2 + 2 * bl : 2 + 2 * bl + tile_hits[t]]
                    for t in range(rows.shape[0])
                ]
            ) if n_hits else np.zeros(0, dtype=rows.dtype)
            pair_sid = np.concatenate(
                [
                    rows[
                        t,
                        2 + 2 * bl + cap_local : 2 + 2 * bl + cap_local
                        + tile_hits[t],
                    ]
                    for t in range(rows.shape[0])
                ]
            ) if n_hits else np.zeros(0, dtype=rows.dtype)
            host_route = true_overflow.copy()
            if route_to_host is not None:
                for i, topic in enumerate(topics):
                    if topic and route_to_host(topic):
                        host_route[i] = True
            return materialize_compact_pairs(
                stats,
                self.topics.subscribers,
                pair_sid,
                pair_shard,
                per_topic,
                host_route,
                n_hits,
                topics,
                None,
                self.window,
                true_overflow,
                tables=tables,
                lazy=self.lazy,
            )

        return resolve_compact

    def _compact_capacity_for(self, b_padded: int) -> int:
        """Pair-buffer capacity for one gathered batch (the shared
        pick_compact_capacity policy), capped at the slot-buffer bound
        the gather could actually fill."""
        max_hits = b_padded * self.n_shards * self.out_slots
        return pick_compact_capacity(
            self.compact_capacity, self._hits_ewma, b_padded, max_hits,
            self._caps,
        )

    def _observe_hits(self, n_hits: int, b: int) -> None:
        self._hits_ewma = fold_hits_ewma(self._hits_ewma, n_hits, b)

    def match_topics(self, topics: list[str], route_to_host=None) -> list[Subscribers]:
        """Match a batch of topics; every result is bit-identical to the
        host trie (overflowing topics are re-walked on host).

        ``route_to_host`` optionally forces extra topics onto the host walk
        (the delta overlay's affected-check); the host path is always
        correct, so any predicate preserves parity."""
        return self.match_topics_async(topics, route_to_host)()

    def subscribers(self, topic: str) -> Subscribers:
        return self.match_topics([topic])[0]

    def _expand(self, tables, shard_sids: np.ndarray, acc) -> Subscribers:
        """Union per-shard local sub ids into one Subscribers set (the C
        materializer when given — same merge semantics, pinned by the
        tests/test_native.py differentials; expand_sids otherwise). The
        caller resolves ``acc`` once per batch, not per topic."""
        subs = Subscribers()
        if acc is not None:
            for s in range(self.n_shards):
                acc.expand_sids_list(
                    shard_sids[s].tolist(), tables[s].snaps, tables[s].window, subs
                )
            return subs
        for s in range(self.n_shards):
            expand_sids(tables[s], shard_sids[s], subs, seen=set())
        return subs


def dryrun_multichip(n_devices: int) -> None:
    """Create an ``n_devices`` mesh, jit the FULL sharded match step (batch
    DP x subscription sharding with an all_gather union over ICI), and run
    one step on tiny shapes. The driver invokes this on a virtual CPU mesh
    to validate the multi-chip path without hardware."""
    # The dryrun runs on virtual CPU devices only and must never
    # initialize an accelerator backend (a chip belongs to one process,
    # and the caller may be on a host whose chip another process holds):
    # pin the platform to cpu (both the env var and the live config) and
    # provision n virtual CPU devices BEFORE the first backend query —
    # clients read their config at first use.
    import os

    prior_platforms = os.environ.get("JAX_PLATFORMS")
    os.environ["JAX_PLATFORMS"] = "cpu"
    try:
        jax.config.update("jax_platforms", "cpu")
    except Exception:  # brokerlint: ok=R4 backend already initialized; the cpu query below still tries
        pass
    try:
        _dryrun_body(n_devices)
    finally:
        # the in-process pin is unavoidably sticky once jax initializes, but
        # the env mutation must not leak into child processes spawned later
        if prior_platforms is None:
            os.environ.pop("JAX_PLATFORMS", None)
        else:
            os.environ["JAX_PLATFORMS"] = prior_platforms


def _dryrun_body(n_devices: int) -> None:
    import os
    import re

    try:
        # only ever raise the count — the config value overrides a larger
        # XLA_FLAGS request, so clamping down would break later callers
        m = re.search(
            r"--xla_force_host_platform_device_count=(\d+)",
            os.environ.get("XLA_FLAGS", ""),
        )
        current = max(
            int(m.group(1)) if m else 1,
            int(jax.config.jax_num_cpu_devices or 0),
        )
        jax.config.update("jax_num_cpu_devices", max(n_devices, current))
        provisioned = True
    except RuntimeError:  # the cpu backend was initialized before us
        provisioned = False
    # query ONLY the cpu backend: a bare jax.devices() initializes the
    # default platform, i.e. takes the chip on a TPU host
    try:
        devices = jax.devices("cpu")
    except RuntimeError:
        # backends already initialized under a platform set without cpu
        devices = []
    if len(devices) < n_devices:
        # last resort, for a host whose backends were already initialized
        # before this call (so CPU provisioning couldn't apply) but which
        # has n real accelerators: run on those. Never reached when the CPU
        # provisioning above succeeded, so the driver path stays CPU-only.
        try:
            all_devices = jax.devices()
            if len(all_devices) >= n_devices:
                devices = all_devices
        except Exception:  # brokerlint: ok=R4 last-resort device query; the count check below raises the real error
            pass
    if len(devices) < n_devices:
        raise RuntimeError(
            f"need {n_devices} devices, have {len(devices)}"
            + (
                ""
                if provisioned
                else " — a JAX backend was initialized before dryrun_multichip()"
                " could provision virtual CPU devices; call it first in the"
                " process or set XLA_FLAGS=--xla_force_host_platform_device_count"
                f"={n_devices} before starting python"
            )
        )
    devices = devices[:n_devices]
    mesh = make_mesh(devices)
    from ..packets import Subscription

    index = TopicsIndex()
    filters = ["a/b/c", "a/+/c", "a/#", "d/e", "+/e", "x/y/z", "q/+/+", "#"]
    for i, flt in enumerate(filters * 4):
        index.subscribe(f"cl{i}", Subscription(filter=flt, qos=i % 3))
    matcher = ShardedTpuMatcher(index, mesh=mesh, max_levels=4, out_slots=32)
    try:
        topics = ["a/b/c", "d/e", "x/y/z", "q/w/e", "nope", "a/z/c", "e", "a/b"]
        results = matcher.match_topics(topics)
        # verify against the host oracle — the dryrun must not just compile
        for topic, dev in zip(topics, results):
            host = index.subscribers(topic)
            assert set(dev.subscriptions) == set(host.subscriptions), (
                topic, set(dev.subscriptions), set(host.subscriptions)
            )
        # exercise the incremental path: one mutation must dirty exactly one
        # shard and still produce oracle-identical results after rebuild
        index.subscribe("late", Subscription(filter="a/b/c", qos=1))
        index.unsubscribe("d/e", "cl3")
        for topic in topics:
            dev = matcher.subscribers(topic)
            host = index.subscribers(topic)
            assert set(dev.subscriptions) == set(host.subscriptions), topic
    finally:
        matcher.close()
    # the live-broker configuration: DeltaMatcher folding trie churn over a
    # mesh-sharded snapshot (the round-2 regression shipped because no
    # driver check covered this combination)
    from ..ops.delta import DeltaMatcher

    dm = DeltaMatcher(index, mesh=mesh, max_levels=4, background=False)
    try:
        index.subscribe("churn", Subscription(filter="a/+/c", qos=1))
        for topic in topics:
            dev = dm.subscribers(topic)  # overlay: churned topics host-route
            host = index.subscribers(topic)
            assert set(dev.subscriptions) == set(host.subscriptions), topic
        dm.flush()  # fold the overlay into a fresh per-shard snapshot
        assert dm.pending_deltas == 0
        for topic in topics:
            dev = dm.subscribers(topic)
            host = index.subscribers(topic)
            assert set(dev.subscriptions) == set(host.subscriptions), topic
    finally:
        dm.close()
