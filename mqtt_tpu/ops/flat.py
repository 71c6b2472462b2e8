"""The flat-hash device matcher index: wildcard matching as a multi-probe
hash join instead of a trie walk.

Why not a trie walk on device: TPU random gathers serialize per index
regardless of table size, while each index can fetch a whole row for the
same price. A per-level NFA walk costs O(levels x frontier x search)
gathered elements per topic (the retired CSR kernel); a whole-path hash
join costs O(P) row fetches, where P is the number of *globally distinct
wildcard shapes* in the filter set — a property of the workload that real
MQTT subscription sets keep tiny (a handful of `+` layouts and `#`
depths).

Encoding (reference semantics: topics.go:583-628):

- Every terminal trie path becomes one entry keyed by a 2x u32 whole-path
  hash; `+` levels hash as a sentinel constant, `#` filters are keyed by
  (levels-before-#, kind=HASH).
- The build enumerates the distinct (kind, depth, plus-mask) shapes; a
  topic of n levels probes each EXACT shape with depth == n and each HASH
  shape with depth <= n, substituting the sentinel at the shape's `+`
  positions. Probes are independent -> fully vectorized, one dispatch.
- The wildcard-walk corner cases are properties of entries, not control
  flow: `filter/#` matches `filter` itself only when the filter's LAST
  level is literal (the partKey != "+" rule, topics.go:612) — a per-entry
  `last_plus` flag; that match excludes inline subscriptions (the
  parent-inline quirk, topics.go:615) — reg ids ordered before inl ids;
  `$`-topics never match client subscriptions whose filter starts with a
  top-level wildcard [MQTT-4.7.1-1/2] but shared/inline subscriptions are
  exempt (topics.go:637) — a per-entry top_wild flag plus a per-id exempt
  bit.
- Anything the device cannot prove is routed to the bit-identical host
  trie: probes of saturated buckets and topics deeper than the compiled
  level cap (the mesh-sharded slot form also routes a topic whose ids
  outnumber its ``out_slots``).

Table layout: `table[S, 16]` u32 = 4 slots/bucket x [key1, key2, meta,
base]. Sub ids are SYNTHETIC — entry ordinal x window + slot — so the
kernel computes them from the bucket row alone: matching costs exactly ONE
64-byte row gather per probe shape, and the host maps ids back to
subscriptions lazily (sid // window -> snapshot).

An entry of at most ``window`` ids takes one slot and one ordinal, its
three counts packed into the meta word. A WIDE entry (more ids than the
window: a broadcast filter a thousand clients hold) takes two adjacent
slots of its bucket and ``ceil(n / window)`` CONSECUTIVE ordinals: the
meta word carries the wide flag and zero counts, the slot after it is
``[ncli, nreg, 0, ninl]`` in full 32-bit words, and the id list (clients,
then shared, then inline) is cut into window-sized snapshots, one an
ordinal, each a ``(clients, shared, inline)`` tuple of its own. Its sids
are therefore one contiguous range like any other entry's, the probe
still reads one row, and ``sid // window -> snapshot`` stays two integer
operations for the resolvers (native/accelmod.c ``merge_sid`` and
``_LazySubTable.__getitem__`` are unchanged).
"""

from __future__ import annotations

import threading
from dataclasses import dataclass, field
from functools import partial
from typing import Any

import numpy as np

from ..topics import SHARE_PREFIX, TopicsIndex
from .hashing import hash_token

KIND_CLIENT = 0  # a normal client subscription
KIND_SHARED = 1  # a $SHARE group member
KIND_INLINE = 2  # an in-process inline subscription

# path-hash domain constants (u32 wraparound arithmetic throughout)
_M1 = 0x9E3779B1
_M2 = 0x85EBCA77
PLUS1 = 0x9E3779B9  # sentinel level-hash for '+' (lane 1)
PLUS2 = 0xC2B2AE3D  # sentinel level-hash for '+' (lane 2)
KIND_EXACT = 0x165667B1
KIND_HASH = 0x27D4EB2F

# meta word bit layout (one per entry). A narrow entry's counts are
# window-bounded, so six bits each: ncli (the $-exempt boundary: slots >=
# ncli are shared/inline), nreg (clients+shared — the id count when a '#'
# entry matches its exact depth, which excludes inline), ninl (inline
# tail). A WIDE entry (more ids than the window) sets _WIDE_SHIFT, leaves
# the three fields 0 and keeps its counts as whole words in the bucket's
# NEXT slot, `[ncli, nreg, 0, ninl]` (_EXT_*): word 2 stays 0 so that slot
# never reads as wide itself, and the probe masks it out of the key match.
_CNT_BITS = 6
_NCLI_SHIFT = 0
_NREG_SHIFT = 6
_NINL_SHIFT = 12
_TOPWILD_SHIFT = 18
_LASTPLUS_SHIFT = 19
_WIDE_SHIFT = 20
_SAT_SHIFT = 21  # entry-0 meta only: whole bucket saturated at build
MAX_WINDOW = (1 << _CNT_BITS) - 1
_EXT_NCLI, _EXT_NREG, _EXT_NINL = 0, 1, 3  # words of a wide entry's second slot

ENTRY_INTS = 4
BUCKET_ENTRIES = 4
ROW_INTS = ENTRY_INTS * BUCKET_ENTRIES


def _bucket(n: int, minimum: int = 16) -> int:
    """Smallest power-of-two >= n (at least ``minimum``) — the shape bucket
    that keeps XLA executables reusable across index rebuilds."""
    size = minimum
    while size < n:
        size *= 2
    return size


def _pad_to(a: np.ndarray, size: int, fill) -> np.ndarray:
    if len(a) >= size:
        return a
    return np.concatenate([a, np.full(size - len(a), fill, dtype=a.dtype)])


@dataclass
class SubEntry:
    """Host-side metadata for one device sub id."""

    kind: int
    client: str  # client id (CLIENT/SHARED) or "" (INLINE)
    group_filter: str  # full $SHARE filter (SHARED only)
    subscription: Any  # packets.Subscription or topics.InlineSubscription


@dataclass
class FlatIndex:
    """The device-side flat-hash encoding of the subscription set."""

    table: np.ndarray  # u32[S, 16] — 4 x [k1, k2, meta, base] per bucket
    pat_kind: np.ndarray  # u32[P] — KIND_EXACT / KIND_HASH
    pat_depth: np.ndarray  # i32[P]
    pat_mask: np.ndarray  # u32[P] — '+' level bitmask
    subs: Any = field(default_factory=list)  # _LazySubTable (sid -> SubEntry)
    salt: int = 0
    window: int = 16
    max_levels: int = 8
    n_entries: int = 0
    n_subs: int = 0  # actual subscriptions indexed (sid space is larger)
    n_sat: int = 0  # build-saturated buckets (probes host-route)
    n_wide: int = 0  # entries with more ids than the window (two slots each)
    max_width: int = 0  # most ids any entry has held since the last build
    n_orphans: int = 0  # sid windows abandoned by in-place folds
    # Wildcard-free fast path (SURVEY §7 hard part 4: "host fast-path for
    # exact-match-only tries"): when the filter set has NO '+'/'#' anywhere,
    # matching degenerates to one dict probe — path string -> snapshot
    # tuple — and the device round trip is pure loss. ``exact_map``
    # covers ALL terminal paths, including the over-deep ones and those
    # in saturated buckets that the device table cannot serve, so the
    # fast path has no fallback classes at all. None when the filter set
    # has wildcards (or after a fold introduces one).
    exact_map: Any = None

    @property
    def wildcard_free(self) -> bool:
        """True when the exact-map fast path can serve every topic."""
        return self.exact_map is not None

    @property
    def num_nodes(self) -> int:
        """Entry count (named for continuity with the retired CSR index)."""
        return self.n_entries

    @property
    def num_subs(self) -> int:
        return self.n_subs

    @property
    def num_patterns(self) -> int:
        return int(self.pat_depth.shape[0])

    # -- incremental fold --------------------------------------------------

    def clone_for_fold(self) -> "FlatIndex":
        """The copy-on-write clone a fold mutates: scalar fields and np
        arrays shared, sub table cloned (see ``fold`` for the safety
        contract)."""
        import dataclasses

        return dataclasses.replace(self, subs=self.subs.clone_for_fold())

    def fold(self, index: TopicsIndex, filters) -> "Optional[tuple[list, bool]]":
        """Apply subscription mutations for ``filters`` to this instance
        and return ``(bucket_updates, pats_changed)`` — the device-side
        scatter payload — or ``None`` when only a full rebuild can absorb
        them.

        MUST be called on a copy-on-write clone (``clone_for_fold``), never
        on the instance in-flight resolvers captured: a resolver issued
        generations ago may decode sids for a filter mutated only later —
        its generation's overlay does not host-route that filter, so it
        must keep seeing the snapshot from its own issue time. The np
        ``table``/pat arrays ARE shared with the live instance and
        mutated in place — safe because resolvers never read them (device
        arrays are swapped functionally) — which is also why an aborted
        fold poisons folding until a full rebuild rebuilds them fresh
        (TpuMatcher.fold).

        This is the churn path: a full rebuild of a large index costs
        seconds of host build plus a full-table H2D upload, while a fold
        touches one bucket row per distinct filter path (~KB).

        An entry that crosses the window boundary folds in place, either
        way: it keeps its ordinals when the new id list needs no more of
        them than it holds (the spare ones are orphaned), takes a fresh
        run of consecutive ordinals otherwise, and its bucket row is
        re-packed around the one or two slots it now needs.

        Full-rebuild (``None``) cases: a new wildcard SHAPE with no free
        pad slot in the pattern arrays, a token hashing to the ``+``
        sentinel pair under the current salt, a torn trie read that
        persists across retries, or degradation beyond the compaction
        thresholds (orphaned sid windows; a bucket whose entries, a wide
        one counting two, no longer fit its four slots).
        Residual risk: a new filter whose 64-bit path key collides with a
        different live filter folds into the wrong entry (p ~ 2^-64 x n;
        the same order as the kernel's own topic-key match); the periodic
        full rebuild re-checks uniqueness and re-salts.
        """
        from .hashing import tokenize_topics

        S = self.table.shape[0]
        tbl = self.table.reshape(S, BUCKET_ENTRIES, ENTRY_INTS)
        # compaction threshold: stop folding once orphaned sid windows
        # exceed a quarter of the sid space — with an absolute floor so
        # small indexes (where a full rebuild is cheap anyway, but also
        # where every unsubscribe is a large fraction) never thrash
        if self.n_orphans * self.window > max(4096, len(self.subs) // 4):
            return None
        seen_paths = set()
        touched: set = set()
        pats_changed = False
        empty_snap = ((), (), ())
        window = self.window
        subs = self.subs
        # exact-map maintenance is STAGED and applied only when the whole
        # fold succeeds: the dict is shared with the live instance
        # (clone_for_fold does not copy it — a 1M-entry dict copy would
        # defeat the fold's purpose), so an aborted fold must leave it
        # byte-identical to the snapshot the live instance serves
        map_updates: list = []
        map_disable = False

        for f in filters:
            parts = f.split("/")
            share_rooted = bool(parts) and parts[0].upper() == SHARE_PREFIX
            if share_rooted:
                parts = parts[2:]
            key = tuple(parts)
            if key in seen_paths:
                continue
            seen_paths.add(key)
            is_hash = bool(parts) and parts[-1] == "#"
            levels = parts[:-1] if is_hash else parts
            depth = len(levels)

            # ONE live node snapshot per filter (torn reads retried like
            # the full walk); serves both the exact-map and the bucket fold
            snap = None
            for _attempt in range(8):
                try:
                    node = index._seek(f, 2 if share_rooted else 0)
                    snap = empty_snap if node is None else _node_snap(node)
                    break
                except (RuntimeError, KeyError):
                    continue
            if snap is None:
                return None  # persistent tear: let the full rebuild quiesce

            if self.exact_map is not None and not map_disable:
                if is_hash or "+" in levels:
                    # a wildcard filter ends the exact-only regime; the
                    # fast path disengages until the next full rebuild
                    # re-evaluates the filter set
                    map_disable = True
                else:
                    map_updates.append(
                        ("/".join(parts), None if snap == empty_snap else snap)
                    )
            if depth > self.max_levels:
                continue  # over-deep: host-routed by length, never indexed

            # path key under the current salt (mirrors build_flat_index)
            mask = 0
            for d, tok in enumerate(levels):
                if tok == "+":
                    mask |= 1 << d
            tok1, tok2, _l, _dl, _ov = tokenize_topics(
                ["/".join(levels)], self.max_levels, self.salt
            )
            kind = KIND_HASH if is_hash else KIND_EXACT
            with np.errstate(over="ignore"):
                h1 = np.uint32(depth) * np.uint32(_M2) ^ np.uint32(kind)
                h2 = np.uint32(depth) * np.uint32(_M1) ^ np.uint32(kind)
                for d in range(depth):
                    if (mask >> d) & 1:
                        t1, t2 = np.uint32(PLUS1), np.uint32(PLUS2)
                    else:
                        t1, t2 = tok1[0, d], tok2[0, d]
                        if t1 == PLUS1 and t2 == PLUS2:
                            return None  # sentinel collision: needs a re-salt
                    h1 = _mix_np(h1, t1)
                    h2 = _mix_np(h2, t2)
            h1 = int(h1)
            h2 = int(h2)

            n_cli, n_shr, n_inl = len(snap[0]), len(snap[1]), len(snap[2])
            total = n_cli + n_shr + n_inl

            slot = h1 & (S - 1)
            row = tbl[slot]
            if (int(row[0, 2]) >> _SAT_SHIFT) & 1:
                continue  # saturated bucket: already fully host-routed
            entries = _bucket_entries(row)
            found = None
            for entry in entries:
                if entry[0] == h1 and entry[1] == h2:
                    found = entry
                    break
            if found is None and total == 0:
                continue  # deleted before we ever indexed it

            top_wild = bool(parts) and parts[0] in ("+", "#")
            last_plus = is_hash and depth > 0 and ((mask >> (depth - 1)) & 1) == 1
            flags = (int(top_wild) << _TOPWILD_SHIFT) | (
                int(last_plus) << _LASTPLUS_SHIFT
            )
            if total > window:
                meta = flags | (1 << _WIDE_SHIFT)
                counts = (n_cli, n_cli + n_shr, n_inl)
            else:
                meta = (
                    flags
                    | (n_cli << _NCLI_SHIFT)
                    | ((n_cli + n_shr) << _NREG_SHIFT)
                    | (n_inl << _NINL_SHIFT)
                )
                counts = None
            chunks = _chunk_snaps(snap, window) if total else []

            if found is not None:
                _ncli, old_nreg, old_ninl = _entry_counts(found)
                old_k = _ordinals_for(old_nreg + old_ninl, window)
                ordinal = found[3] // window
                if len(chunks) > old_k:
                    # more windows than it holds: the old run is orphaned
                    # whole and the entry moves to a fresh consecutive one
                    for j in range(old_k):
                        subs.replace(ordinal + j, empty_snap)
                    self.n_orphans += old_k
                    if len(subs) + len(chunks) * window >= 1 << 30:
                        return None  # sid space: the rebuild re-packs it
                    ordinal = subs.extend(chunks)
                else:
                    for j, chunk in enumerate(chunks):
                        subs.replace(ordinal + j, chunk)
                    for j in range(len(chunks), old_k):
                        subs.replace(ordinal + j, empty_snap)
                    self.n_orphans += old_k - len(chunks)
                self.n_subs += total - old_nreg - old_ninl
                self.n_wide += int(counts is not None) - int(found[4] is not None)
                if total == 0:
                    entries.remove(found)
                    self.n_entries -= 1
                else:
                    found[2:] = [meta, ordinal * window, counts]
            else:
                # the shape must already be compiled (or claim a pad slot)
                claim = -1
                for p in range(len(self.pat_depth)):
                    if (
                        self.pat_kind[p] == np.uint32(kind)
                        and self.pat_depth[p] == depth
                        and self.pat_mask[p] == np.uint32(mask)
                    ):
                        claim = -1
                        break
                    if claim < 0 and self.pat_depth[p] < 0:
                        claim = p
                else:
                    if claim < 0:
                        return None  # pads exhausted: recompile needed
                if len(subs) + len(chunks) * window >= 1 << 30:
                    return None  # sid space: the rebuild re-packs it
                entries.append(
                    [h1, h2, meta, subs.extend(chunks) * window, counts]
                )
                self.n_subs += total
                self.n_wide += int(counts is not None)
                self.n_entries += 1
            if sum(1 if e[4] is None else 2 for e in entries) > BUCKET_ENTRIES:
                # fold-time saturation would orphan the bucket's OTHER
                # entries — filters that are NOT in the delta overlay,
                # so in-flight batches could still decode their sids
                # against emptied snapshots. Only the full rebuild
                # (which swaps a fresh FlatIndex wholesale, leaving
                # captured snapshots intact) can absorb this safely.
                return None
            if found is None and claim >= 0:
                self.pat_kind[claim] = np.uint32(kind)
                self.pat_depth[claim] = np.int32(depth)
                self.pat_mask[claim] = np.uint32(mask)
                pats_changed = True
            _write_bucket(row, entries)
            if total > self.max_width:
                self.max_width = total
            touched.add(slot)

        # the fold succeeded: apply the staged exact-map maintenance. The
        # dict is shared with the live instance; mutating it here (before
        # the owner swaps this clone in) is safe for the same reason the
        # in-place np table edits are — every filter touched is in the
        # delta overlay, so in-flight resolvers host-route it
        if map_disable:
            self.exact_map = None
        elif self.exact_map is not None:
            for key_str, map_snap in map_updates:
                if map_snap is None:
                    self.exact_map.pop(key_str, None)
                else:
                    self.exact_map[key_str] = map_snap

        flat_rows = self.table  # [S, ROW_INTS] view of the same buffer
        updates = [(s, flat_rows[s].copy()) for s in sorted(touched)]
        return updates, pats_changed


def _ordinals_for(total: int, window: int) -> int:
    """Consecutive ordinals an entry of ``total`` ids is laid over."""
    return max(1, -(-total // window))


def _chunk_snaps(snap: tuple, window: int) -> list:
    """Cut one ``(clients, shared, inline)`` snapshot into the
    window-sized snapshots a wide entry's consecutive ordinals hold: the
    id list is clients, then shared, then inline, and chunk ``j`` is its
    ids ``[j * window, (j + 1) * window)``, again as a 3-tuple, so
    ``sid // window`` finds the chunk and ``sid % window`` the id in it
    by the same clients-shared-inline arithmetic a narrow entry uses."""
    cli, shr, inl = snap
    nc, ns = len(cli), len(shr)
    total = nc + ns + len(inl)
    if total <= window:
        return [snap]
    return [
        (
            cli[lo : lo + window],
            shr[max(0, lo - nc) : max(0, lo + window - nc)],
            inl[max(0, lo - nc - ns) : max(0, lo + window - nc - ns)],
        )
        for lo in range(0, total, window)
    ]


def _bucket_entries(row: np.ndarray) -> list:
    """One bucket row ``[4, 4]`` as its live entries, in slot order:
    ``[k1, k2, meta, base, counts]`` with ``counts`` the
    ``(ncli, nreg, ninl)`` of a wide entry's second slot, None for a
    narrow one (its counts are in ``meta``)."""
    out = []
    words = row.tolist()
    e = 0
    while e < BUCKET_ENTRIES:
        k1, k2, meta, base = words[e]
        e += 1
        if not (k1 or k2 or meta or base):
            continue
        counts = None
        if (meta >> _WIDE_SHIFT) & 1:
            ext = words[e]
            counts = (ext[_EXT_NCLI], ext[_EXT_NREG], ext[_EXT_NINL])
            e += 1
        out.append([k1, k2, meta, base, counts])
    return out


def _entry_counts(entry: list) -> tuple:
    """``(ncli, nreg, ninl)`` of one parsed entry, wide or narrow."""
    if entry[4] is not None:
        return entry[4]
    meta = entry[2]
    return (
        (meta >> _NCLI_SHIFT) & MAX_WINDOW,
        (meta >> _NREG_SHIFT) & MAX_WINDOW,
        (meta >> _NINL_SHIFT) & MAX_WINDOW,
    )


def _write_bucket(row: np.ndarray, entries: list) -> None:
    """Re-pack a bucket row from its parsed entries (at most four slots'
    worth: the caller checked), a wide entry's counts in the slot after
    it."""
    row[:] = 0
    e = 0
    for k1, k2, meta, base, counts in entries:
        row[e] = (k1, k2, meta, base)
        e += 1
        if counts is not None:
            row[e, _EXT_NCLI], row[e, _EXT_NREG], row[e, _EXT_NINL] = counts
            e += 1


def _mix_np(h: np.ndarray, t: np.ndarray) -> np.ndarray:
    h = (h ^ t).astype(np.uint32)
    h = ((h << np.uint32(13)) | (h >> np.uint32(19))).astype(np.uint32)
    return (h * np.uint32(_M1)).astype(np.uint32)


class _LazySubTable:
    """sid -> SubEntry, materialized on demand from per-ordinal snapshot
    tuples (clients, shared, inline) captured at build time: a narrow
    entry's one, a wide entry's window-sized chunks on consecutive
    ordinals (``_chunk_snaps``). Sub ids are synthetic — ordinal x
    window + slot — so the mapping is two integer ops. Memoized: hot
    topics resolve to dict hits."""

    __slots__ = ("_window", "_snaps", "_n", "memo")

    def __init__(self, window, snaps, n) -> None:
        self._window = window
        self._snaps = snaps
        self._n = n
        self.memo: dict = {}  # public: expand_sids probes it directly

    def __len__(self) -> int:
        return self._n

    @property
    def snaps(self) -> list:
        """The raw snapshot tuples, indexed by entry ordinal — the C
        materializer (native/accelmod.c) walks these directly."""
        return self._snaps

    @property
    def window(self) -> int:
        """Slots per entry ordinal (sid = ordinal * window + slot)."""
        return self._window

    def __getitem__(self, sid: int) -> SubEntry:
        entry = self.memo.get(sid)
        if entry is not None:
            return entry
        cli, shr, inl = self._snaps[sid // self._window]
        local = sid % self._window
        if local < len(cli):
            client, sub = cli[local]
            entry = SubEntry(KIND_CLIENT, client, "", sub)
        elif local < len(cli) + len(shr):
            client, sub = shr[local - len(cli)]
            entry = SubEntry(KIND_SHARED, client, sub.filter, sub)
        else:
            entry = SubEntry(KIND_INLINE, "", "", inl[local - len(cli) - len(shr)])
        self.memo[sid] = entry
        return entry

    # -- fold support (FlatIndex.fold) ------------------------------------

    def clone_for_fold(self) -> "_LazySubTable":
        """A copy-on-write clone for one fold: the snaps list is copied
        (refs only) so in-flight resolvers that captured THIS table keep
        their snapshot untouched; the memo starts empty (hot sids
        re-materialize in one batch). The clone is what fold mutates."""
        return _LazySubTable(self._window, list(self._snaps), self._n)

    def replace(self, ordinal: int, snap) -> None:
        """Swap one entry's snapshot (only ever called on a fold clone)."""
        self._snaps[ordinal] = snap
        w = self._window
        memo_pop = self.memo.pop
        for sid in range(ordinal * w, ordinal * w + w):
            memo_pop(sid, None)

    def extend(self, snaps: list) -> int:
        """Allocate a fresh run of consecutive ordinals, one a snapshot
        (a narrow entry's one, a wide entry's chunks), and return the
        first (fold clones only)."""
        ordinal = len(self._snaps)
        self._snaps.extend(snaps)
        self._n += self._window * len(snaps)
        return ordinal


def _node_snap(node) -> tuple:
    """Capture one trie node's subscriptions as an immutable snapshot
    tuple ``(clients, shared, inline)`` — the unit both the sid table and
    the exact-map fast path serve from. Reads the live maps without the
    lock (tears retry, same contract as ``_walk_terminals``)."""
    subs, shared, inline = node.subscriptions, node.shared, node.inline_subscriptions
    cli = tuple(subs.internal.items()) if subs is not None else ()
    shr = (
        tuple(
            (c, s)
            for group in shared.internal.values()
            for c, s in group.items()
        )
        if shared is not None
        else ()
    )
    inl = tuple(inline.internal.values()) if inline is not None else ()
    return (cli, shr, inl)


def _walk_terminals(index: TopicsIndex):
    """Yield (path_levels, particle) for every trie node carrying
    subscriptions: a particle names a map only while the map holds
    something. Iterative (deep tries must not recurse) and lock-free:
    it reads the live maps without copying, so a concurrent structural
    mutation can tear the walk with RuntimeError/KeyError — callers retry
    (the same contract the sharded rebuild documents)."""
    stack = [(index.root, [])]
    while stack:
        p, path = stack.pop()
        if (
            p.subscriptions is not None
            or p.shared is not None
            or p.inline_subscriptions is not None
        ):
            yield path, p
        for key, child in p.particles.items():
            stack.append((child, path + [key]))


def build_flat_index(
    index: TopicsIndex,
    max_levels: int = 8,
    salt: int = 0,
    window: int = 16,
    min_buckets: int = 1024,
    cooperative: bool = False,
    _retries: int = 6,
) -> FlatIndex:
    """Compile the host trie into a :class:`FlatIndex`.

    Retries with a fresh salt when (a) two distinct paths collide on the
    64-bit key or (b) a real token hashes to the `+` sentinel pair
    (probability ~2^-64 each). Filters deeper than ``max_levels`` are
    omitted: every topic they could match is deeper than ``max_levels``
    too and therefore host-routed before probing.
    """
    import time as _time

    # cooperative mode (background rebuilds): yield the GIL periodically so
    # the serving thread's match latency stays flat during multi-second
    # builds — this is what keeps the churn benchmark's p99 honest
    yield_every = 4096 if cooperative else 0
    paths: list[list[str]] = []
    nodes = []
    for path, p in _walk_terminals(index):
        paths.append(path)
        nodes.append(p)
        if yield_every and len(paths) % yield_every == 0:
            _time.sleep(0)
    n_all = len(paths)

    # per-entry shape + level strings
    is_hash = np.zeros(n_all, dtype=bool)
    keep = np.ones(n_all, dtype=bool)
    depths = np.zeros(n_all, dtype=np.int32)
    masks = np.zeros(n_all, dtype=np.uint32)
    level_strs: list[list[str]] = []
    any_wild = False  # any '+'/'#' anywhere (incl. over-deep paths)
    for i, path in enumerate(paths):
        hsh = bool(path) and path[-1] == "#"
        if hsh or "+" in path:
            any_wild = True
        levels = path[:-1] if hsh else path
        if len(levels) > max_levels:
            keep[i] = False
            level_strs.append([])
            continue
        is_hash[i] = hsh
        depths[i] = len(levels)
        m = 0
        for d, tok in enumerate(levels):
            if tok == "+":
                m |= 1 << d
        masks[i] = m
        level_strs.append(levels)

    # level token hashes via the native batch tokenizer (tokens never
    # contain '/', so the '/'-joined path re-tokenizes losslessly); '+'
    # levels are overwritten with the sentinel pair afterwards
    from .hashing import tokenize_topics

    tok1, tok2, _lens, _dollar, _ovf = tokenize_topics(
        ["/".join(levels) if levels else "" for levels in level_strs],
        max_levels,
        salt,
    )
    tok1 = tok1.copy()
    tok2 = tok2.copy()
    level_idx = np.arange(max_levels)[None, :]
    in_depth = level_idx < depths[:, None]
    plus_at = ((masks[:, None] >> level_idx.astype(np.uint32)) & 1) == 1
    # a real token hashing to the sentinel pair would fake a '+' match
    if bool(np.any(in_depth & ~plus_at & (tok1 == PLUS1) & (tok2 == PLUS2))):
        if _retries <= 0:
            raise RuntimeError("persistent '+' sentinel collision")
        return build_flat_index(
            index, max_levels, salt + 1, window, min_buckets, cooperative,
            _retries - 1
        )
    tok1[plus_at & in_depth] = PLUS1
    tok2[plus_at & in_depth] = PLUS2
    # zero out beyond-depth lanes so the mix loop's `use` mask semantics
    # match the per-entry construction exactly
    tok1[~in_depth] = 0
    tok2[~in_depth] = 0

    # whole-path hashes (vectorized over entries, looped over levels)
    kind_w = np.where(is_hash, np.uint32(KIND_HASH), np.uint32(KIND_EXACT))
    with np.errstate(over="ignore"):
        h1 = (depths.astype(np.uint32) * np.uint32(_M2)) ^ kind_w
        h2 = (depths.astype(np.uint32) * np.uint32(_M1)) ^ kind_w
        for d in range(max_levels):
            use = d < depths
            h1 = np.where(use, _mix_np(h1, tok1[:, d]), h1)
            h2 = np.where(use, _mix_np(h2, tok2[:, d]), h2)

    sel = np.nonzero(keep)[0]
    key64 = (h1[sel].astype(np.uint64) << np.uint64(32)) | h2[sel].astype(np.uint64)
    if len(np.unique(key64)) != len(key64):  # distinct paths collided
        if _retries <= 0:
            raise RuntimeError("persistent path-key collision")
        return build_flat_index(
            index, max_levels, salt + 1, window, min_buckets, cooperative,
            _retries - 1
        )

    # per-entry subscription snapshots. A sub id is SYNTHETIC — entry
    # ordinal x window + slot (clients first, then shared, then inline) —
    # so nothing per-subscription is built or stored. SubEntry metadata
    # materializes lazily at expand time from the snapshot tuples
    # (:class:`_LazySubTable`), preserving build-time snapshot semantics.
    snaps: list = [None] * n_all
    n_cli = np.zeros(n_all, dtype=np.int64)
    n_shr = np.zeros(n_all, dtype=np.int64)
    n_inl = np.zeros(n_all, dtype=np.int64)
    top_wilds = np.zeros(n_all, dtype=bool)
    for k, i in enumerate(sel):
        node = nodes[i]
        path = paths[i]
        if yield_every and k % yield_every == 0:
            _time.sleep(0)
        top_wilds[i] = bool(path) and path[0] in ("+", "#")
        # .internal (no locked copy): tears retry, see _walk_terminals
        cli, shr, inl = snaps[i] = _node_snap(node)
        n_cli[i] = len(cli)
        n_shr[i] = len(shr)
        n_inl[i] = len(inl)
    total_ids = n_cli + n_shr + n_inl
    if window > MAX_WINDOW:
        raise ValueError(
            f"window must be <= {MAX_WINDOW} (meta packs counts in "
            f"{_CNT_BITS}-bit fields); got {window}"
        )
    # an entry of more ids than the window is WIDE: it is laid over
    # ceil(n / window) consecutive ordinals, a window-sized snapshot each,
    # and takes two slots of its bucket (its counts do not fit the meta
    # word). Narrow entries keep ordinals 0..n_narrow-1 in walk order; the
    # wide ones' runs follow, so a table without any is built as before
    wide = total_ids > window
    n = len(sel)
    sel_wide = wide[sel]
    wide_idx = sel[sel_wide]
    n_wide = len(wide_idx)
    n_narrow = n - n_wide
    runs = -(-total_ids[wide_idx] // window)  # ordinals a wide entry
    n_ordinals = n_narrow + int(runs.sum())
    n_sids = n_ordinals * window
    if n_sids >= 1 << 30:
        # sid arithmetic is int32 end to end; leave sign-bit headroom
        raise RuntimeError(
            f"flat index sid space must stay < {1 << 30}, got {n_sids}"
        )
    ordinal = np.zeros(n_all, dtype=np.int64)
    ordinal[sel[~sel_wide]] = np.arange(n_narrow)
    ordinal[wide_idx] = n_narrow + np.cumsum(runs) - runs
    starts = (ordinal * window).astype(np.uint32)  # the per-entry 4th word
    nclis = np.where(wide, 0, n_cli).astype(np.uint32)
    nregs = np.where(wide, 0, n_cli + n_shr).astype(np.uint32)
    ninls = np.where(wide, 0, n_inl).astype(np.uint32)
    n_subs_total = int(total_ids[sel].sum())
    flat_snaps = [snaps[i] for i in sel[~sel_wide].tolist()]
    for i in wide_idx.tolist():
        flat_snaps.extend(_chunk_snaps(snaps[i], window))
    subs = _LazySubTable(window, flat_snaps, n_sids)

    # size for ~0.6 slots per 4-slot bucket: P(bucket > 4 | Poisson 0.6)
    # ~ 3e-4, so saturation host-routes a negligible probe fraction
    need = 1 + sel_wide.astype(np.int64)  # slots an entry takes
    S = _bucket(max(min_buckets, int(need.sum() / 0.6) + 1), minimum=1024)
    slot = (h1[sel] & np.uint32(S - 1)).astype(np.int64)
    order = np.argsort(slot, kind="stable")
    sslot = slot[order]
    first = np.searchsorted(sslot, sslot, side="left")
    before = np.cumsum(need[order]) - need[order]
    rank = before - before[first]  # an entry's first slot within its bucket
    used = np.bincount(slot, weights=need, minlength=S)
    sat = used > BUCKET_ENTRIES
    n_sat = int(sat.sum())

    meta = (
        (nclis[sel] << np.uint32(_NCLI_SHIFT))
        | (nregs[sel] << np.uint32(_NREG_SHIFT))
        | (ninls[sel] << np.uint32(_NINL_SHIFT))
        | (top_wilds[sel].astype(np.uint32) << np.uint32(_TOPWILD_SHIFT))
        | (
            (is_hash[sel] & (depths[sel] > 0) & (((masks[sel] >> (depths[sel] - 1).astype(np.uint32)) & 1) == 1)).astype(np.uint32)
            << np.uint32(_LASTPLUS_SHIFT)
        )
        | (sel_wide.astype(np.uint32) << np.uint32(_WIDE_SHIFT))
    )
    table = np.zeros((S, BUCKET_ENTRIES, ENTRY_INTS), dtype=np.uint32)
    ok = ~sat[slot[order]]
    o = order[ok]
    cols = np.stack([h1[sel][o], h2[sel][o], meta[o], starts[sel][o]], axis=1)
    table[slot[o], rank[ok]] = cols
    if n_wide:
        ow = ok & sel_wide[order]
        w = sel[order[ow]]
        ext = np.zeros((len(w), ENTRY_INTS), dtype=np.uint32)
        ext[:, _EXT_NCLI] = n_cli[w]
        ext[:, _EXT_NREG] = n_cli[w] + n_shr[w]
        ext[:, _EXT_NINL] = n_inl[w]
        table[slot[order[ow]], rank[ow] + 1] = ext
    table[np.nonzero(sat)[0], 0, 2] = np.uint32(1 << _SAT_SHIFT)
    table = table.reshape(S, ROW_INTS)

    # distinct probe shapes, power-of-two padded (pads have depth -1 and are
    # never active) so churn rebuilds keep the jit signature stable
    shape_keys = np.stack(
        [kind_w[sel], depths[sel].astype(np.uint32), masks[sel]], axis=1
    )
    if len(shape_keys):
        uniq = np.unique(shape_keys, axis=0)
    else:
        uniq = np.zeros((0, 3), dtype=np.uint32)
    pat_kind = uniq[:, 0].astype(np.uint32)
    pat_depth = uniq[:, 1].astype(np.int32)
    pat_mask = uniq[:, 2].astype(np.uint32)
    if len(uniq):
        pb = _bucket(len(uniq), minimum=2)
        pat_kind = _pad_to(pat_kind, pb, np.uint32(KIND_EXACT))
        pat_depth = _pad_to(pat_depth, pb, np.int32(-1))
        pat_mask = _pad_to(pat_mask, pb, np.uint32(0))

    # wildcard-free fast path: every terminal path (kept and over-deep
    # alike) keyed by its literal path string — one dict probe
    # replaces the whole device round trip (FlatIndex.exact_map)
    exact_map = None
    if not any_wild:
        exact_map = {}
        for i in sel:
            exact_map["/".join(level_strs[i])] = snaps[i]
        for i in np.nonzero(~keep)[0]:
            exact_map["/".join(paths[i])] = _node_snap(nodes[i])

    return FlatIndex(
        table=table,
        pat_kind=pat_kind,
        pat_depth=pat_depth,
        pat_mask=pat_mask,
        subs=subs,
        salt=salt,
        window=window,
        max_levels=max_levels,
        n_entries=n,
        n_subs=n_subs_total,
        n_sat=n_sat,
        n_wide=n_wide,
        max_width=int(total_ids[sel].max()) if n else 0,
        exact_map=exact_map,
    )


# ---------------------------------------------------------------------------
# device kernel
# ---------------------------------------------------------------------------


def _probe_head(
    table, pat_kind, pat_depth, pat_mask, tok1, tok2, lengths, is_dollar,
    *, max_levels
):
    """The shared probe stage: whole-path hashes, ONE bucket row gather per
    probe, hit/meta decode, and the per-probe surviving id range
    ``[base+lo, base+lo+cnt)`` (synthetic ids make every probe's result a
    contiguous range; the $-mask drops exactly the client prefix). A
    wide entry's three counts come from the slot after it in the SAME
    row, so its ``cnt`` runs past the window and nothing else differs.
    Returns ``(start[B,P] i32, cnt[B,P] i32, overflow[B] bool,
    wide[B] bool)``: ``overflow`` is a probe of a saturated bucket,
    ``wide`` a topic whose answer holds a wide entry's hit."""
    import jax.numpy as jnp

    B, L = tok1.shape
    P = pat_depth.shape[0]
    S = table.shape[0]
    m1 = jnp.uint32(_M1)
    m2 = jnp.uint32(_M2)

    def rotl13(x):
        return (x << jnp.uint32(13)) | (x >> jnp.uint32(19))

    # whole-path pattern hashes [B, P], sentinel at each pattern's '+' levels
    kd = pat_depth.astype(jnp.uint32)
    h1 = jnp.broadcast_to((kd * m2 ^ pat_kind)[None, :], (B, P))
    h2 = jnp.broadcast_to((kd * m1 ^ pat_kind)[None, :], (B, P))
    for d in range(max_levels):
        use = (d < pat_depth)[None, :]
        plus = ((pat_mask >> np.uint32(d)) & 1)[None, :] == 1
        t1 = jnp.where(plus, jnp.uint32(PLUS1), tok1[:, d][:, None])
        t2 = jnp.where(plus, jnp.uint32(PLUS2), tok2[:, d][:, None])
        h1 = jnp.where(use, rotl13(h1 ^ t1) * m1, h1)
        h2 = jnp.where(use, rotl13(h2 ^ t2) * m1, h2)

    n = lengths[:, None]  # [B, 1]
    hash_pat = (pat_kind == jnp.uint32(KIND_HASH))[None, :]
    active = jnp.where(hash_pat, pat_depth[None, :] <= n, pat_depth[None, :] == n)

    # ONE bucket row per probe: [B, P, 16]
    slot = jnp.where(active, (h1 & jnp.uint32(S - 1)).astype(jnp.int32), 0)
    rows = table[slot].reshape(B, P, BUCKET_ENTRIES, ENTRY_INTS)

    hit = (rows[..., 0] == h1[..., None]) & (rows[..., 1] == h2[..., None])
    # the slot after a wide entry holds its counts, not a key
    counts_slot = ((rows[..., :-1, 2] >> _WIDE_SHIFT) & 1) == 1  # [B, P, 3]
    keyed = jnp.concatenate(
        [jnp.ones((B, P, 1), bool), ~counts_slot], axis=-1
    )
    hit = hit & keyed & active[..., None]  # [B, P, 4]; at most one per probe
    meta = jnp.where(hit, rows[..., 2], 0).max(axis=-1)
    base = jnp.where(hit, rows[..., 3], 0).max(axis=-1)
    hit_any = hit.any(axis=-1)
    sat_probe = ((rows[:, :, 0, 2] >> _SAT_SHIFT) & 1) == 1

    cnt_mask = (1 << _CNT_BITS) - 1
    wide = ((meta >> _WIDE_SHIFT) & 1) == 1
    # a wide entry never sits in the bucket's last slot: its counts follow
    hit_w = hit[..., :-1]

    def count(shift, word):
        in_meta = (meta >> shift) & cnt_mask
        in_next = jnp.where(hit_w, rows[..., 1:, word], 0).max(axis=-1)
        return jnp.where(wide, in_next, in_meta).astype(jnp.int32)

    ncli = count(_NCLI_SHIFT, _EXT_NCLI)
    nreg = count(_NREG_SHIFT, _EXT_NREG)
    ninl = count(_NINL_SHIFT, _EXT_NINL)
    top_wild = (meta >> _TOPWILD_SHIFT) & 1
    last_plus = (meta >> _LASTPLUS_SHIFT) & 1

    # 'filter/#' matching the exact-length topic: only via a literal last
    # level (topics.go:612), and without inline subs (topics.go:615)
    exact_len = pat_depth[None, :] == n
    valid_hit = hit_any & ~(hash_pat & exact_len & (last_plus == 1))
    count = jnp.where(hash_pat & exact_len, nreg, nreg + ninl)
    count = jnp.where(valid_hit, count, 0)

    # $-topics never match top-level-wildcard CLIENT subscriptions
    # [MQTT-4.7.1-1/2]; clients occupy the window prefix [0, ncli)
    dollar = is_dollar[:, None] & (top_wild == 1)
    lo = jnp.where(dollar, jnp.minimum(ncli, count), 0)  # [B, P]
    cnt = count - lo
    start = base.astype(jnp.int32) + lo
    overflow = (sat_probe & active).any(axis=1)
    return start, cnt, overflow, (wide & (cnt > 0)).any(axis=1)


def flat_match_core(
    table,
    pat_kind,
    pat_depth,
    pat_mask,
    tok1,
    tok2,
    lengths,
    is_dollar,
    *,
    max_levels: int,
    out_slots: int,
    overflow_slots: int = 0,
):
    """Match ``B`` topics against the flat index in one dispatch,
    expanding results to sid slots (the mesh-sharded path's form: slot
    arrays concatenate across shards under ``all_gather``).

    Returns ``(sub_ids[B, out_slots] int32 (-1 padded), totals[B] int32,
    overflow[B] bool)`` — ``overflow`` marks topics the host must re-walk
    (saturated-bucket probe, or more matches than
    ``overflow_slots``/``out_slots``: a wide entry's hit is slotted like
    any other while the topic's ids fit, so on this form a filter that
    more subscribers hold than a shard has slots still takes the host
    route). Pure jnp; jit/shard_map-able (mqtt_tpu.parallel shards the
    table's bucket axis across a device mesh)."""
    import jax.numpy as jnp

    B, L = tok1.shape
    P = pat_depth.shape[0]
    if P == 0:  # empty index: nothing matches, nothing overflows
        return (
            jnp.full((B, out_slots), -1, jnp.int32),
            jnp.zeros((B,), jnp.int32),
            jnp.zeros((B,), bool),
        )
    start, cnt, overflow, _wide = _probe_head(
        table, pat_kind, pat_depth, pat_mask, tok1, tok2, lengths, is_dollar,
        max_levels=max_levels,
    )
    offs = jnp.cumsum(cnt, axis=1)  # inclusive [B, P]
    totals = offs[:, -1]
    prev = offs - cnt  # exclusive
    ks = jnp.arange(out_slots, dtype=jnp.int32)  # [K]
    # which probe supplies out slot k: the first p with offs[p] > k
    sel_onehot = (prev[:, None, :] <= ks[None, :, None]) & (
        ks[None, :, None] < offs[:, None, :]
    )  # [B, K, P]
    sel = sel_onehot.astype(jnp.int32)
    # out slot k = start + (k - prev) of its probe: one fused reduction
    comb = (start - prev)[:, None, :]
    in_range = ks[None, :] < totals[:, None]
    out = jnp.where(in_range, ks[None, :] + (sel * comb).sum(axis=2), -1)
    overflow = overflow | (totals > (overflow_slots or out_slots))
    return out, totals, overflow


def flat_match_ranges_core(
    table,
    pat_kind,
    pat_depth,
    pat_mask,
    tok1,
    tok2,
    lengths,
    is_dollar,
    *,
    max_levels: int,
):
    """Match ``B`` topics, emitting per-probe sid RANGES instead of
    expanded slots: ``(start[B,P] i32, cnt[B,P] i32, totals[B] i32,
    overflow[B] bool)``.

    This is the single-device production form: synthetic ids make every
    probe's surviving result one contiguous range, so ranges carry the
    COMPLETE result in 2P ints/topic — no transfer-prefix cap (and no
    host fallback class for it), no device-side compaction, whatever the
    width of the entries hit. ``overflow`` = saturated-bucket probe
    only."""
    start, cnt, totals, flags = _ranges_flags(
        table, pat_kind, pat_depth, pat_mask, tok1, tok2, lengths, is_dollar,
        max_levels=max_levels,
    )
    return start, cnt, totals, (flags & FLAG_OVERFLOW) != 0


# the served programs' per-topic flags word: the host re-walks a topic
# with FLAG_OVERFLOW; FLAG_WIDE only says its answer holds a wide entry's
# hit (MatcherStats.wide_topics) and routes nothing
FLAG_OVERFLOW = 1
FLAG_WIDE = 2


def _ranges_flags(
    table, pat_kind, pat_depth, pat_mask, tok1, tok2, lengths, is_dollar,
    *, max_levels
):
    """``flat_match_ranges_core`` with the two per-topic facts of the
    probe in one i32 word, as the packed and compacted programs carry
    them: ``(start, cnt, totals, flags[B])``."""
    import jax.numpy as jnp

    B, L = tok1.shape
    P = pat_depth.shape[0]
    if P == 0:  # empty index: honor the [B, P] contract with P = 0
        return (
            jnp.zeros((B, 0), jnp.int32),
            jnp.zeros((B, 0), jnp.int32),
            jnp.zeros((B,), jnp.int32),
            jnp.zeros((B,), jnp.int32),
        )
    start, cnt, overflow, wide = _probe_head(
        table, pat_kind, pat_depth, pat_mask, tok1, tok2, lengths, is_dollar,
        max_levels=max_levels,
    )
    flags = overflow * jnp.int32(FLAG_OVERFLOW) + wide * jnp.int32(FLAG_WIDE)
    return start, cnt, cnt.sum(axis=1), flags


def _jit_core():
    import jax

    return partial(jax.jit, static_argnames=("max_levels", "out_slots", "overflow_slots"))(
        flat_match_core
    )


class _LazyJit:
    """Defer the jax.jit wrapping until first call (keeps `import
    mqtt_tpu.ops` light and CPU-only test processes fast). ``builder``
    returns the jitted callable. When ``kernel`` is named, the built
    callable is wrapped in a devicestats.KernelWatch so every first
    call per (shapes, dtypes, statics) signature lands in the
    compile-event ledger — the single ``note_compile`` seam for the
    flat/predicates/recrypt/retained kernel families (ISSUE 18). The
    first build also places the persistent compilation cache
    (ops/backend.ensure_compile_cache) before anything compiles."""

    def __init__(self, builder, kernel=None):
        self._builder = builder
        self._kernel = kernel
        self._fn = None
        self._lock = threading.Lock()

    def __call__(self, *args, **kwargs):
        if self._fn is None:
            with self._lock:
                if self._fn is None:
                    from .backend import ensure_compile_cache

                    ensure_compile_cache()
                    built = self._builder()
                    if self._kernel is not None:
                        from .devicestats import KernelWatch

                        built = KernelWatch(self._kernel, built)
                    self._fn = built
        return self._fn(*args, **kwargs)


flat_match = _LazyJit(_jit_core, kernel="flat_match")


def pack_tokens(tok1, tok2, lengths, is_dollar) -> np.ndarray:
    """Pack a tokenized batch into ONE int32 host array ``[B, 2L+2]`` so a
    match call performs a single H2D transfer instead of four."""
    return np.concatenate(
        [
            tok1.view(np.int32),
            tok2.view(np.int32),
            lengths[:, None].astype(np.int32),
            is_dollar[:, None].astype(np.int32),
        ],
        axis=1,
    )


def _packed_core(
    table,
    pat_kind,
    pat_depth,
    pat_mask,
    packed_tokens,
    *,
    max_levels,
):
    """The production single-device form: ONE packed input transfer and
    ONE packed RANGES output transfer. In ``[B, 2L+2]`` i32, out
    ``[B, 2P+2]`` i32 = (range starts | range counts | total | flags:
    FLAG_OVERFLOW, FLAG_WIDE).
    Ranges carry the complete result (flat_match_ranges_core), so there is
    no transfer-prefix host-fallback class and no device-side compaction;
    2P ints/topic also transfer less than any useful slot prefix."""
    import jax
    import jax.numpy as jnp

    L = (packed_tokens.shape[1] - 2) // 2
    tok1 = jax.lax.bitcast_convert_type(packed_tokens[:, :L], jnp.uint32)
    tok2 = jax.lax.bitcast_convert_type(packed_tokens[:, L : 2 * L], jnp.uint32)
    lengths = packed_tokens[:, 2 * L]
    is_dollar = packed_tokens[:, 2 * L + 1].astype(bool)
    start, cnt, totals, flags = _ranges_flags(
        table,
        pat_kind,
        pat_depth,
        pat_mask,
        tok1,
        tok2,
        lengths,
        is_dollar,
        max_levels=max_levels,
    )
    return jnp.concatenate(
        [start, cnt, totals[:, None], flags[:, None]], axis=1
    )


def _compact_core(
    table,
    pat_kind,
    pat_depth,
    pat_mask,
    packed_tokens,
    *,
    max_levels,
    capacity,
):
    """Device-resident hit compaction (ROADMAP item 1): match ``B`` topics
    and compact every real hit into packed ``(topic_idx, subscriber_id)``
    pairs ON DEVICE, so the D2H transfer scales with the hits that exist
    (~``hits x 8`` bytes) instead of the padded result geometry.

    The probe head emits per-probe contiguous sid ranges; a segmented
    prefix-sum over the ``[B, P]`` count matrix assigns each output slot
    its source segment — each non-empty segment scatters its id at its
    first output slot and a running max fills the gaps (O(B*P + K),
    where a searchsorted formulation costs O(K log(B*P)) and measurably
    dominates the whole match kernel on wide capacities) — and the
    slot's sid is recomputed from the segment's range start: no host
    expansion, no per-topic padding.

    Output: ONE int32 vector ``[2 + 2B + capacity]`` =
    ``(n_hits, batch_overflow | totals[B] | flags[B] (FLAG_OVERFLOW,
    FLAG_WIDE) | pair_sid[capacity])`` (-1-padded). The pair stream is TOPIC-MAJOR,
    so each pair's topic_idx is reconstructed for free on the host by
    walking the per-topic totals — the logical ``(topic_idx, sid)``
    pair moves 4 bytes, not 8. ``n_hits`` is the TRUE hit count even
    when it exceeds ``capacity``: the host uses it to size the next
    batch's capacity, and ``batch_overflow`` routes THIS batch onto the
    padded-ranges path (compaction never guesses — an overflowing batch
    pays one extra round trip, a fitting batch transfers only its
    hits)."""
    import jax
    import jax.numpy as jnp

    L = (packed_tokens.shape[1] - 2) // 2
    tok1 = jax.lax.bitcast_convert_type(packed_tokens[:, :L], jnp.uint32)
    tok2 = jax.lax.bitcast_convert_type(packed_tokens[:, L : 2 * L], jnp.uint32)
    lengths = packed_tokens[:, 2 * L]
    is_dollar = packed_tokens[:, 2 * L + 1].astype(bool)
    B = lengths.shape[0]
    P = pat_depth.shape[0]
    if P == 0:  # empty index: no hits, nothing overflows
        z = jnp.zeros((B,), jnp.int32)
        return jnp.concatenate(
            [
                jnp.zeros((2,), jnp.int32),
                z,
                z,
                jnp.full((capacity,), -1, jnp.int32),
            ]
        )
    start, cnt, totals, flags = _ranges_flags(
        table,
        pat_kind,
        pat_depth,
        pat_mask,
        tok1,
        tok2,
        lengths,
        is_dollar,
        max_levels=max_levels,
    )
    c_flat = cnt.reshape(B * P)
    cum = jnp.cumsum(c_flat)  # inclusive prefix sum over segments
    offs = cum - c_flat  # exclusive
    n_hits = cum[-1]
    seg_c = _segment_of_slot(c_flat, offs, capacity)
    k = jnp.arange(capacity, dtype=jnp.int32)
    sid = start.reshape(-1)[seg_c] + (k - offs[seg_c].astype(jnp.int32))
    valid = k < n_hits
    header = jnp.stack(
        [n_hits, (n_hits > capacity).astype(jnp.int32)]
    )
    return jnp.concatenate(
        [
            header,
            totals,
            flags,
            jnp.where(valid, sid, -1),
        ]
    )


def _segment_of_slot(c_flat, offs, capacity: int):
    """Which segment supplies each compacted output slot: every
    non-empty segment scatters ``id + 1`` at its first output offset,
    a running max fills the runs, minus one recovers the id. O(S + K)
    device work. Slots past the real hit count read the last marked
    segment — callers mask them with their own validity test; a
    segment whose offset lands past ``capacity`` clips onto the last
    slot, which only happens on a batch that overflows (and therefore
    falls back) anyway."""
    import jax
    import jax.numpy as jnp

    n_segs = c_flat.shape[0]
    seg_ids = jnp.arange(n_segs, dtype=jnp.int32)
    nonzero = c_flat > 0
    targets = jnp.where(
        nonzero, jnp.minimum(offs, capacity - 1), capacity - 1
    ).astype(jnp.int32)
    marks = jnp.zeros((capacity,), jnp.int32).at[targets].max(
        jnp.where(nonzero, seg_ids + 1, 0)
    )
    seg = jax.lax.cummax(marks) - 1
    return jnp.clip(seg, 0, n_segs - 1)


def _jit_compact():
    import jax

    # no donation: the only per-call input is the ``[B, 2L+2]`` token
    # buffer, and no output (one ``[2 + 2B + capacity]`` vector) has its
    # shape, so XLA could never alias it — jax just warns "Some donated
    # buffers were not usable" at every compile (seen on the v5e, PR 21)
    return partial(jax.jit, static_argnames=("max_levels", "capacity"))(
        _compact_core
    )


flat_match_compact = _LazyJit(_jit_compact, kernel="flat_match_compact")


def _scatter_core(table, idx, rows):
    """Functional bucket-row scatter: the fold's device-side update. The
    caller pads ``idx``/``rows`` to a power-of-two length by repeating the
    last pair — duplicate indices write identical rows, so the update
    order XLA picks is immaterial."""
    return table.at[idx].set(rows)


def _jit_scatter():
    import jax

    return jax.jit(_scatter_core, donate_argnums=())


scatter_rows = _LazyJit(_jit_scatter, kernel="scatter_rows")


def _jit_ranges():
    import jax

    return partial(jax.jit, static_argnames=("max_levels",))(
        flat_match_ranges_core
    )


flat_match_ranges = _LazyJit(_jit_ranges, kernel="flat_match_ranges")


def _jit_packed():
    import jax

    return partial(jax.jit, static_argnames=("max_levels",))(_packed_core)


flat_match_packed = _LazyJit(_jit_packed, kernel="flat_match_packed")
