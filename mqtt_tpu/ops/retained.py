"""Device-resident retained matching: the publish CSR walk run in reverse.

A wildcard SUBSCRIBE against millions of retained topics is the mirror
image of the publish hot path: PUBLISH asks "which of P patterns match
this one topic", retained delivery asks "which of B topics match this one
pattern". Both are the same hash-join the flat kernel (mqtt_tpu.ops.flat)
already computes — so this engine reuses it verbatim with the roles
swapped: the single SUBSCRIBE filter becomes a one-pattern flat index
(``build_flat_index`` over a throwaway one-subscription trie) and the
retained topic NAMES become the tokenized topic batch. One packed H2D
transfer, one ``flat_match_packed`` dispatch, and the totals column names
every retained topic the filter reaches.

Correctness is anchored to the HOST walk (``TopicsIndex.messages``), the
same way the publish matcher is anchored to ``subscribers()``:

- **Namespace partitioning.** The retained corpus is kept per tenant
  namespace (mqtt_tpu.topics ``NS_CHAR`` scoping) with LOCALIZED names, so
  the walk's structural guards — a global wildcard never enters a
  namespace subtree, a tenant filter never leaves one — hold by
  construction instead of by kernel emulation.
- **``$SYS`` protection.** The walk hides the ``$SYS`` subtree from
  top-level wildcards ([MQTT-4.7.1-1/2]) but walks into other
  ``$``-prefixed roots. The kernel's dollar rule is driven by the
  tokenizer's ``is_dollar`` flag, so the engine OVERRIDES it to "first
  LOCAL level == $SYS" — bit-identical to the walk's guard, including the
  ``$other/...`` corner the plain ``startswith("$")`` flag would get
  wrong.
- **``#`` base-topic divergence.** Spec 4.7.1.2 (and the kernel) lets
  ``a/#`` match the topic ``a`` itself; the retained walk deliberately
  collects only strictly-deeper children. A host-side post-filter drops
  hits whose level count equals a ``#``-filter's base depth, restoring
  the walk's semantics exactly.
- **Fallback classes.** Anything the kernel geometry cannot represent —
  corpus topics or filters deeper than ``max_levels``, kernel probe
  overflow, a filter the one-pattern index could not seat — routes the
  whole call to the host walk and is COUNTED per class; capacity is never
  a correctness event.
- **Differential oracle + breaker.** Every Nth served match replays the
  host walk and compares topic-name sets (the established
  matcher/predicate/recrypt oracle pattern). The host wins any mismatch,
  which feeds a :class:`~mqtt_tpu.resilience.CircuitBreaker`; an open
  breaker degrades ALL retained matching to the host walk and heals
  through fully-verified probes — a device fault storm costs throughput,
  never a missed retained delivery.
"""

from __future__ import annotations

import threading
from typing import Any, Dict, List, Optional

import numpy as np

from ..packets import Subscription
from ..resilience import CircuitBreaker
from ..topics import NS_CHAR, TopicsIndex, ns_local, ns_tenant
from .flat import FLAG_OVERFLOW, build_flat_index, flat_match_packed
from .hashing import tokenize_topics

# host-fallback classes (counted; mirrors flat.py's fallback accounting)
FALLBACK_CLASSES = ("depth", "filter", "overflow", "error", "breaker")

_MIN_CAPACITY = 1024  # padded corpus floor: bounds JIT shape churn


def _is_sys_local(name: str) -> bool:
    """The walk's guard predicate: first LOCAL level is exactly $SYS."""
    return name == "$SYS" or name.startswith("$SYS/")


class _NsCorpus:
    """One namespace's retained-name corpus with an incrementally-built
    packed token matrix. Tombstoned rows keep their stale tokens (dropped
    host-side by the ``names[i] is None`` check) until the tombstone
    ratio forces a compaction rebuild."""

    __slots__ = ("names", "pos", "tombstones", "packed", "overflow", "n_tok")

    def __init__(self) -> None:
        self.names: List[Optional[str]] = []
        self.pos: Dict[str, int] = {}
        self.tombstones = 0
        self.packed: Optional[np.ndarray] = None
        self.overflow: Optional[np.ndarray] = None
        self.n_tok = 0  # rows of `names` covered by `packed`

    def active(self) -> int:
        return len(self.names) - self.tombstones


class RetainedMatchEngine:
    """Batched retained-topic matching for wildcard SUBSCRIBE, device
    kernel first, host walk as oracle and refuge."""

    def __init__(
        self,
        index: TopicsIndex,
        max_levels: int = 8,
        oracle_sample: int = 16,
        breaker: Optional[CircuitBreaker] = None,
        min_capacity: int = _MIN_CAPACITY,
        rebuild_ratio: float = 0.25,
    ) -> None:
        self.index = index
        self.max_levels = max_levels
        # 1-in-N differential sampling (0 disables the sampled oracle;
        # probe re-closes always verify fully)
        self.oracle_sample = max(0, oracle_sample)
        self.breaker = breaker or CircuitBreaker()
        self.min_capacity = max(1, min_capacity)
        self.rebuild_ratio = rebuild_ratio
        self._corpora: Dict[str, _NsCorpus] = {}
        self._fidx_cache: Dict[str, Any] = {}  # local filter -> FlatIndex
        self._lock = threading.Lock()  # anonymous: corpus/cache bookkeeping
        self._calls = 0
        self.device_matches = 0
        self.oracle_checks = 0
        self.oracle_mismatches = 0
        self.fallbacks: Dict[str, int] = {k: 0 for k in FALLBACK_CLASSES}

    # -- corpus maintenance --------------------------------------------------

    def note_retained(self, topic: str, retained: bool) -> None:
        """Track one scoped retained-topic mutation (server calls this
        from ``retain_message`` and the restore path)."""
        ns = ns_tenant(topic)
        local = ns_local(topic)
        with self._lock:
            c = self._corpora.get(ns)
            if c is None:
                if not retained:
                    return
                c = self._corpora[ns] = _NsCorpus()
            if retained:
                if local not in c.pos:
                    c.pos[local] = len(c.names)
                    c.names.append(local)
            else:
                i = c.pos.pop(local, None)
                if i is not None:
                    c.names[i] = None
                    c.tombstones += 1
                    if c.tombstones > self.rebuild_ratio * max(1, len(c.names)):
                        self._compact(c)

    def reseed(self) -> int:
        """Rebuild every corpus from the trie's retained store (restart
        restore / drift repair). Returns the corpus size."""
        snapshot = self.index.retained.get_all()
        corpora: Dict[str, _NsCorpus] = {}
        for topic in snapshot:
            ns = ns_tenant(topic)
            c = corpora.get(ns)
            if c is None:
                c = corpora[ns] = _NsCorpus()
            local = ns_local(topic)
            c.pos[local] = len(c.names)
            c.names.append(local)
        with self._lock:
            self._corpora = corpora
        return len(snapshot)

    def _compact(self, c: _NsCorpus) -> None:
        """Drop tombstones and force retokenization (lock held)."""
        c.names = [n for n in c.names if n is not None]
        c.pos = {n: i for i, n in enumerate(c.names) if n is not None}
        c.tombstones = 0
        c.packed = None
        c.overflow = None
        c.n_tok = 0

    def _ensure_tokens(self, c: _NsCorpus) -> None:
        """Tokenize rows appended since the last match (lock held). The
        packed matrix is padded to a power-of-two capacity (zero rows:
        harmless, never read host-side) so kernel shapes — and therefore
        JIT compilations — stay bounded."""
        n = len(c.names)
        width = 2 * self.max_levels + 2
        cap = self.min_capacity
        while cap < n:
            cap *= 2
        if c.packed is None or c.packed.shape[0] < cap:
            packed = np.zeros((cap, width), dtype=np.int32)
            overflow = np.zeros(cap, dtype=bool)
            if c.packed is not None and c.n_tok:
                packed[: c.n_tok] = c.packed[: c.n_tok]
                overflow[: c.n_tok] = c.overflow[: c.n_tok]  # type: ignore[index]
            c.packed, c.overflow = packed, overflow
        if c.n_tok < n:
            fresh = [x if x is not None else "" for x in c.names[c.n_tok : n]]
            tok1, tok2, lengths, _dollar, over = tokenize_topics(
                fresh, self.max_levels, 0
            )
            # the $SYS guard override (module docstring): NOT startswith("$")
            dollar = np.fromiter(
                (_is_sys_local(x) for x in fresh), dtype=bool, count=len(fresh)
            )
            L = self.max_levels
            assert c.packed is not None and c.overflow is not None
            c.packed[c.n_tok : n, :L] = tok1.view(np.int32)
            c.packed[c.n_tok : n, L : 2 * L] = tok2.view(np.int32)
            c.packed[c.n_tok : n, 2 * L] = lengths.astype(np.int32)
            c.packed[c.n_tok : n, 2 * L + 1] = dollar.astype(np.int32)
            c.overflow[c.n_tok : n] = over
            c.n_tok = n

    # -- filter index --------------------------------------------------------

    def _filter_index(self, local_filter: str):
        """A one-pattern flat index for the SUBSCRIBE filter (cached —
        fleets re-subscribe the same wildcard filters constantly), or
        None when the kernel cannot represent it."""
        fidx = self._fidx_cache.get(local_filter)
        if fidx is not None:
            return fidx
        tmp = TopicsIndex()
        tmp.subscribe("\x00probe", Subscription(filter=local_filter, qos=0))
        fidx = build_flat_index(
            tmp, max_levels=self.max_levels, salt=0, min_buckets=64
        )
        if fidx.n_entries != 1 or fidx.salt != 0:
            return None  # over-deep filter omitted, or salt re-rolled
        if len(self._fidx_cache) >= 512:
            self._fidx_cache.pop(next(iter(self._fidx_cache)))
        self._fidx_cache[local_filter] = fidx
        return fidx

    # -- matching ------------------------------------------------------------

    def _host_names(self, filter: str) -> List[str]:
        return [pk.topic_name for pk in self.index.messages(filter)]

    def _device_names(self, filter: str) -> Optional[List[str]]:
        """The kernel leg: scoped retained names matching ``filter``, or
        None with the fallback class counted."""
        ns = ns_tenant(filter)
        local = ns_local(filter)
        if len(local.split("/")) > self.max_levels:
            self.fallbacks["depth"] += 1
            return None
        with self._lock:
            c = self._corpora.get(ns)
            if c is None or c.active() == 0:
                return []
            self._ensure_tokens(c)
            assert c.packed is not None and c.overflow is not None
            n = len(c.names)
            if bool(c.overflow[:n].any()):
                # an over-deep retained topic exists in this namespace:
                # the kernel cannot see its deep levels, so the walk
                # serves the whole namespace
                self.fallbacks["depth"] += 1
                return None
            names = list(c.names)
            packed = c.packed
        fidx = self._filter_index(local)
        if fidx is None:
            self.fallbacks["filter"] += 1
            return None
        out = np.asarray(
            flat_match_packed(
                fidx.table,
                fidx.pat_kind,
                fidx.pat_depth,
                fidx.pat_mask,
                packed,
                max_levels=self.max_levels,
            )
        )
        p = fidx.pat_kind.shape[0]
        totals = out[: len(names), 2 * p]
        if bool((out[: len(names), 2 * p + 1] & FLAG_OVERFLOW).any()):
            self.fallbacks["overflow"] += 1
            return None
        hits = [i for i in range(len(names)) if names[i] is not None and totals[i] > 0]
        if local == "#" or local.endswith("/#"):
            # the walk's strictly-deeper `#` semantics (module docstring)
            base = len(local.split("/")) - 1
            hits = [
                i
                for i in hits
                if len(names[i].split("/")) != base  # type: ignore[union-attr]
            ]
        self.device_matches += 1
        if ns:
            return [NS_CHAR + ns + "/" + names[i] for i in hits]  # type: ignore[operator]
        return [names[i] for i in hits]  # type: ignore[misc]

    def match(self, filter: str) -> Optional[List[str]]:
        """Scoped retained topic names matching a scoped WILDCARD
        filter, or None when the caller must run the host walk itself
        (breaker open, capacity fallback, non-wildcard filter)."""
        local = ns_local(filter)
        if "+" not in local and "#" not in local:
            return None  # exact filters take the walk's O(1) fast path
        if local.startswith("$SHARE/"):
            return None  # shared filters get no retained delivery
        if not self.breaker.allow():
            if not self.breaker.acquire_probe():
                self.fallbacks["breaker"] += 1
                return None
            # probe: serve device but verify FULLY against the walk
            try:
                names = self._device_names(filter)
            except Exception:
                self.breaker.record_probe_failure("error")
                self.fallbacks["error"] += 1
                return None
            if names is None:
                self.breaker.record_probe_failure("fallback")
                return None
            host = self._host_names(filter)
            if sorted(host) != sorted(names):
                self.oracle_mismatches += 1
                self.breaker.record_probe_failure("mismatch")
                return host  # host wins the disagreement
            self.breaker.record_probe_success()
            return names
        try:
            names = self._device_names(filter)
        except Exception:
            self.log_error()
            self.breaker.record_failure("error")
            self.fallbacks["error"] += 1
            return None
        if names is None:
            return None
        self._calls += 1
        if self.oracle_sample and self._calls % self.oracle_sample == 0:
            self.oracle_checks += 1
            host = self._host_names(filter)
            if sorted(host) != sorted(names):
                self.oracle_mismatches += 1
                self.breaker.record_failure("mismatch")
                return host  # host wins; breaker counts the fault
            self.breaker.record_success()
        return names

    def log_error(self) -> None:  # split out so tests can silence it
        import logging

        logging.getLogger("mqtt_tpu.ops").exception(
            "retained device match failed; host walk serves"
        )

    # -- introspection -------------------------------------------------------

    def stats(self) -> Dict[str, Any]:
        with self._lock:
            corpus = sum(c.active() for c in self._corpora.values())
        return {
            "corpus": corpus,
            "device_matches": self.device_matches,
            "oracle_checks": self.oracle_checks,
            "oracle_mismatches": self.oracle_mismatches,
            "fallbacks": dict(self.fallbacks),
            "breaker_state": self.breaker.state,
        }
