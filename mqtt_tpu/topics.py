"""Topic trie: subscriptions, shared subscriptions, inline subscriptions,
retained messages, wildcard match walks, and topic aliases.

Behavioral parity with reference ``topics.go`` — this host implementation is
the bit-identical oracle (and fallback path) for the device matcher in
``mqtt_tpu.ops``. The corner cases that define "bit-identical":

- ``zen/#`` matches ``zen`` (spec 4.7.1.2), via the child-``#`` gather at the
  terminal level (topics.go:612-616).
- ``a/b`` must NOT match ``a/b/c`` (no prefix inheritance).
- ``$``-prefixed topics are not matched by TOP-LEVEL ``+``/``#`` filters
  [MQTT-4.7.1-1/2]; the check is on the subscription's original filter string
  (topics.go:637).
- Empty levels are real levels: ``/a/`` is ``["", "a", ""]``.
- ``#`` is gathered at every walk level; ``+`` forks the frontier.
- Shared subscriptions (``$SHARE/<group>/<filter>``) root their subtree at
  depth 2 (topics.go:407-411).

Quirk replicated on purpose (topics.go:615): in the terminal child-``#``
branch, the reference gathers the *parent* particle's inline subscriptions
again instead of the wild child's — so an inline subscription on ``a/#``
does not match topic ``a``.
"""

from __future__ import annotations

import re
import threading
import time
from contextlib import contextmanager
from dataclasses import dataclass, field
from typing import Callable, Optional

from .packets import Packet, PacketStore, Subscription
from .utils import LockedMap

SHARE_PREFIX = "$SHARE"  # prefix indicating a shared-subscription filter
SYS_PREFIX = "$SYS"  # prefix indicating a system info topic

# -- tenant namespaces (mqtt_tpu.tenancy) -----------------------------------
#
# A tenant's topic space is a structurally enforced namespace: every key
# the broker stores or matches for a tenant client — trie filters,
# retained topics, $SHARE inner filters, cluster interest summaries —
# is prefixed with one extra level ``NS_CHAR + tenant`` before it
# reaches this module. NS_CHAR is U+0000, which no client-supplied
# topic or filter may contain ([MQTT-4.7.3-2], enforced by
# ``is_valid_filter``), so a scoped key can never be forged from the
# wire and two tenants' identical topic strings land on disjoint trie
# subtrees. Cross-tenant delivery is therefore impossible by
# construction; the only cross-namespace reach a wildcard has is a
# GLOBAL (untenanted) top-level ``+``/``#`` filter, which the gather
# guards below exclude from namespace subtrees the same way the
# [MQTT-4.7.1-1/2] rule excludes ``$``-topics.

NS_CHAR = "\x00"


def ns_scope_topic(tenant: str, topic: str) -> str:
    """Prefix a tenant-local topic NAME into its namespace."""
    return NS_CHAR + tenant + "/" + topic


def ns_scope_filter(tenant: str, filter: str) -> str:
    """Prefix a tenant-local FILTER into its namespace. A shared
    subscription scopes its inner filter (the group is a delivery
    policy, not an address): ``$SHARE/g/f`` -> ``$SHARE/g/<ns>/f`` —
    the trie roots shared subtrees at depth 2, so two tenants' identical
    groups+filters still land on disjoint particles."""
    if is_shared_filter(filter):
        parts = filter.split("/", 2)
        inner = parts[2] if len(parts) > 2 else ""
        return f"{parts[0]}/{parts[1]}/{NS_CHAR}{tenant}/{inner}"
    return NS_CHAR + tenant + "/" + filter


def ns_tenant(key: str) -> str:
    """The tenant a scoped key belongs to ("" for global keys)."""
    if key[:1] != NS_CHAR:
        return ""
    i = key.find("/")
    return key[1:i] if i > 0 else key[1:]


def ns_local(key: str) -> str:
    """Strip the namespace level off a scoped key (identity for global
    keys) — the tenant-local topic/filter the client sees on the wire."""
    if key[:1] != NS_CHAR:
        return key
    i = key.find("/")
    return key[i + 1 :] if i >= 0 else ""


def _ns_local0(key: str) -> str:
    """First character of the tenant-local portion of a (possibly
    scoped) key — the character the [MQTT-4.7.1-1/2] ``$``-rules apply
    to inside a namespace."""
    if key[:1] != NS_CHAR:
        return key[:1]
    i = key.find("/")
    return key[i + 1 : i + 2] if i >= 0 else ""

# -- MQTT+ predicate suffixes (mqtt_tpu.predicates) -------------------------
#
# An MQTT+ subscription rides a standard SUBSCRIBE filter with a payload
# predicate appended: ``sensors/+/temp$GT{25.0}``. The trie only ever sees
# the BASE filter — the suffix is split off at SUBSCRIBE time so the walk,
# retained matching, and $SHARE parsing are byte-identical to a plain
# subscription. The split is defined here (string surgery is the topic
# layer's business); compilation/evaluation live in mqtt_tpu.predicates.

#: ops that compare a numeric payload feature against a threshold
PREDICATE_NUMERIC_OPS = ("GT", "GTE", "LT", "LTE", "EQ", "NE")
#: ops that aggregate a numeric payload feature over a message window
PREDICATE_AGG_OPS = ("MEAN", "MAX", "MIN")
#: every recognized simple predicate op (CONTAINS and EQS are the
#: payload-bytes/string ops; compounds AND/OR are parsed separately)
PREDICATE_OPS = (
    PREDICATE_NUMERIC_OPS + ("CONTAINS", "EQS") + PREDICATE_AGG_OPS
)
#: compound ops combining SIMPLE predicates: ``$AND{$GT{t:20}$LT{t:30}}``
PREDICATE_COMPOUND_OPS = ("AND", "OR")

_PREDICATE_RE = re.compile(
    r"^(?P<base>.*?)\$(?P<op>" + "|".join(PREDICATE_OPS) + r")\{(?P<arg>[^{}]*)\}$",
    re.DOTALL,
)
# one SIMPLE predicate token, anchored at the string start — the unit
# the compound-argument scanner consumes
_PREDICATE_TOKEN_RE = re.compile(
    r"^\$(?P<op>" + "|".join(PREDICATE_OPS) + r")\{(?P<arg>[^{}]*)\}",
    re.DOTALL,
)
_COMPOUND_RE = re.compile(
    r"^(?P<base>.*?)\$(?P<op>AND|OR)\{(?P<arg>.*)\}$", re.DOTALL
)


def _predicate_arg_ok(op: str, arg: str) -> bool:
    """Validate a predicate argument for ``op`` — an invalid argument means
    the whole token is NOT a predicate (the filter stays literal, so the
    extension can never reject a filter plain MQTT would accept)."""
    if op == "CONTAINS":
        return len(arg) > 0
    if op == "EQS":
        # string equality ``field:literal``; an empty field means "the
        # whole payload as the string"
        _field, sep, _literal = arg.partition(":")
        return bool(sep)
    field_part, _, num = arg.rpartition(":")
    if op in PREDICATE_AGG_OPS:
        try:
            return int(num) >= 1
        except ValueError:
            return False
    try:
        value = float(num)
    except ValueError:
        return False
    return value == value  # reject an explicit nan threshold
    # (field_part may be empty: "whole payload as the number")


def split_predicate_tokens(arg: str) -> tuple:
    """Scan a compound argument into its simple ``$OP{...}`` member
    tokens. Returns the token tuple, or () when the argument is not a
    well-formed run of >= 2 valid simple predicates (compounds of one
    are just that predicate; spell it plainly)."""
    tokens = []
    rest = arg
    while rest:
        m = _PREDICATE_TOKEN_RE.match(rest)
        if m is None or not _predicate_arg_ok(m.group("op"), m.group("arg")):
            return ()
        if m.group("op") in PREDICATE_AGG_OPS:
            # stateful windows have no boolean verdict to combine
            return ()
        tokens.append(m.group(0))
        rest = rest[len(m.group(0)):]
    return tuple(tokens) if len(tokens) >= 2 else ()


def split_predicate_suffix(filter: str) -> tuple[str, str]:
    """Split a trailing MQTT+ predicate off a subscription filter.

    Returns ``(base_filter, suffix)`` where ``suffix`` is the literal
    ``$OP{arg}`` text ("" when the filter carries no well-formed
    predicate). Only a syntactically valid suffix is split — anything
    else is a literal filter, so pre-MQTT+ behavior is bit-identical. A
    bare predicate (``$CONTAINS{alarm}``) means "every topic": the base
    widens to ``#``.

    Compounds (``$AND{...}``/``$OR{...}`` over simple predicates) are
    matched FIRST — their argument contains nested braces, which the
    simple-token grammar deliberately excludes."""
    m = _COMPOUND_RE.match(filter)
    if m is not None and split_predicate_tokens(m.group("arg")):
        base = m.group("base") or "#"
        return base, filter[len(m.group("base")):]
    m = _PREDICATE_RE.match(filter)
    if m is None:
        return filter, ""
    if not _predicate_arg_ok(m.group("op"), m.group("arg")):
        return filter, ""
    base = m.group("base")
    if base == "":
        base = "#"  # payload-only subscription: predicate over all topics
    return base, filter[len(m.group("base")):]


def summary_base(filter: str) -> str:
    """The filter as PUBLISHES match it — the key the mesh interest
    summaries index (mqtt_tpu.mesh_topology): a ``$SHARE/<group>/...``
    subscription strips to the inner filter (publishes arrive on the
    inner topic space, the group is a delivery policy), and a trailing
    MQTT+ predicate strips to its base filter (the predicate gates
    delivery at the subscriber's worker, not routability — a remote
    ``sensors/+/temp$GT{25}`` subscriber still needs the publish
    forwarded before it can evaluate anything)."""
    if is_shared_filter(filter):
        parts = filter.split("/", 2)
        filter = parts[2] if len(parts) > 2 else ""
    base, _suffix = split_predicate_suffix(filter)
    return base


@dataclass(frozen=True)
class Mutation:
    """One subscription mutation, delivered to trie observers.

    Device-index consumers (``mqtt_tpu.ops.delta``, ``mqtt_tpu.parallel``)
    use it to maintain delta overlays and per-shard subscription replicas
    without re-walking the trie.
    """

    filter: str
    kind: str  # "sub" (client/shared subscription) or "inline"
    op: str  # "add" or "del"
    client: str = ""  # client id for kind="sub"; "" for inline
    subscription: Optional[object] = None  # the added Subscription / InlineSubscription
    identifier: int = 0  # inline subscription identifier (kind="inline")


def isolate_particle(filter: str, d: int) -> tuple[str, bool]:
    """Extract the topic level at depth ``d`` and whether more levels follow.

    Depths past the last level clamp to the last level (reference
    topics.go:679-698) — the retained-message ``#`` walk relies on this.
    """
    parts = filter.split("/")
    if d >= len(parts):
        return parts[-1], False
    return parts[d], d < len(parts) - 1


def is_shared_filter(filter: str) -> bool:
    prefix, _ = isolate_particle(filter, 0)
    return prefix.upper() == SHARE_PREFIX


def is_valid_filter(filter: str, for_publish: bool = False) -> bool:
    """Validate a topic filter (or topic name when ``for_publish``);
    reference topics.go:707-745.

    COUPLING NOTE: ``Server.try_fast_publish`` (server.py) short-circuits
    QoS0 v4 publishes using raw-byte gates that must remain a strict
    SUPERSET of this function's ``for_publish`` rejections (it defers all
    ``$``-prefixed, wildcard, NUL, and empty topics to the decode path).
    If a new publish-topic rejection is added here whose topics would
    still pass those byte gates, extend the fast-path gates too."""
    if not for_publish and len(filter) == 0:
        return False  # [MQTT-4.7.3-1]
    if NS_CHAR in filter:
        # [MQTT-4.7.3-2]: topic names and filters must not include
        # U+0000 — and NS_CHAR doubles as the tenant-namespace marker
        # (mqtt_tpu.tenancy), so a wire topic can never alias into (or
        # out of) another tenant's scoped key space
        return False
    if for_publish:
        # 4.7.2: the server prevents clients using $SYS topic names to
        # exchange messages with other clients.
        if len(filter) >= len(SYS_PREFIX) and filter[: len(SYS_PREFIX)].upper() == SYS_PREFIX:
            return False
        if "+" in filter or "#" in filter:
            return False  # [MQTT-3.3.2-2]
    wildhash = filter.find("#")
    if wildhash >= 0 and wildhash != len(filter) - 1:
        return False  # [MQTT-4.7.1-2]
    prefix, has_next = isolate_particle(filter, 0)
    if prefix.upper() == SHARE_PREFIX:
        if not has_next:
            return False  # [MQTT-4.8.2-1]
        group, has_next = isolate_particle(filter, 1)
        if not has_next:
            return False  # [MQTT-4.8.2-1]
        if "+" in group or "#" in group:
            return False  # [MQTT-4.8.2-2]
    return True


# -- topic aliases ---------------------------------------------------------


class InboundTopicAliases:
    """Aliases received from the client (topics.go:43-64)."""

    def __init__(self, maximum: int) -> None:
        self.maximum = maximum
        self.internal: dict[int, str] = {}
        self._lock = threading.Lock()

    def set(self, id_: int, topic: str) -> str:
        with self._lock:
            if self.maximum == 0:
                return topic
            if topic == "" and id_ in self.internal:
                return self.internal[id_]
            self.internal[id_] = topic
            return topic


class OutboundTopicAliases:
    """Aliases assigned by the broker for messages to the client; ids are
    cursor-allocated 1..maximum (topics.go:67-105)."""

    def __init__(self, maximum: int) -> None:
        self.maximum = maximum
        self.internal: dict[str, int] = {}
        self.cursor = 0
        self._lock = threading.Lock()

    def set(self, topic: str) -> tuple[int, bool]:
        """Returns ``(alias, already_existed)``; ``(0, False)`` when aliases
        are disabled or exhausted."""
        with self._lock:
            if self.maximum == 0:
                return 0, False
            if topic in self.internal:
                return self.internal[topic], True
            if self.cursor + 1 > self.maximum:
                return 0, False
            self.cursor += 1
            self.internal[topic] = self.cursor
            return self.cursor, False


class TopicAliases:
    """Inbound and outbound alias registries for one client (topics.go:21)."""

    def __init__(self, topic_alias_maximum: int) -> None:
        self.inbound = InboundTopicAliases(topic_alias_maximum)
        self.outbound = OutboundTopicAliases(topic_alias_maximum)


# -- subscription containers -----------------------------------------------


class Subscriptions(LockedMap[str, Subscription]):
    """A map of subscriptions, keyed by client id (trie state) or by filter
    (client state) (topics.go:249-301)."""


class SharedSubscriptions:
    """Shared subscriptions for one filter: group -> client id -> sub
    (topics.go:109-187)."""

    def __init__(self) -> None:
        self.internal: dict[str, dict[str, Subscription]] = {}
        self._lock = threading.RLock()

    def add(self, group: str, id_: str, val: Subscription) -> None:
        with self._lock:
            self.internal.setdefault(group, {})[id_] = val

    def delete(self, group: str, id_: str) -> None:
        with self._lock:
            subs = self.internal.get(group)
            if subs is None:
                return
            subs.pop(id_, None)
            if not subs:
                del self.internal[group]

    def get(self, group: str, id_: str) -> Optional[Subscription]:
        with self._lock:
            return self.internal.get(group, {}).get(id_)

    def get_all(self) -> dict[str, dict[str, Subscription]]:
        with self._lock:
            return {group: dict(subs) for group, subs in self.internal.items()}

    def group_len(self) -> int:
        with self._lock:
            return len(self.internal)

    def __len__(self) -> int:
        with self._lock:
            return sum(len(subs) for subs in self.internal.values())


# Signature of an inline (in-process) subscription callback: receives the
# local client, the matched subscription, and the publish packet.
InlineSubFn = Callable[["object", Subscription, Packet], None]


@dataclass(slots=True)
class InlineSubscription(Subscription):
    """An in-process subscription: a Subscription plus a handler callback,
    keyed on the subscription identifier (topics.go:306-309)."""

    handler: InlineSubFn | None = None


class InlineSubscriptions(LockedMap[int, "InlineSubscription"]):
    """Inline subscriptions for one particle, keyed on identifier
    (topics.go:195-246)."""

    def add_inline(self, val: "InlineSubscription") -> None:
        self.add(val.identifier, val)


# Aggregated subscriptions for one client, keyed on filter.
ClientSubscriptions = dict


class Subscribers:
    """The result set of a subscriber scan (topics.go:312-347).

    ``__slots__`` keeps the result object dict-free so the C materializer
    (native/accelmod.c) can build one per matched topic at tp_alloc + four
    dict stores."""

    __slots__ = ("shared", "shared_selected", "subscriptions", "inline_subscriptions")

    def __init__(self) -> None:
        self.shared: dict[str, dict[str, Subscription]] = {}
        self.shared_selected: dict[str, Subscription] = {}
        self.subscriptions: dict[str, Subscription] = {}
        self.inline_subscriptions: dict[int, InlineSubscription] = {}

    def select_shared(self) -> None:
        """Pick one subscriber per shared group. The reference picks the
        first map-iteration entry (nondeterministic in Go); here it is the
        group's first member in insertion order: the member that
        subscribed earliest and still holds the row. A SUBSCRIBE that
        replaces a member's row keeps its place; a clean reconnect of a
        session drops its rows and its SUBSCRIBE adds them again at the
        group's end. Nothing here asks whether the member is connected, so
        an offline session can be chosen while another member is online:
        the group's copy then reaches no socket (``Server._select_shared``
        counts such picks as ``share_picks_offline``). Selection stays
        host-side and pluggable via the on_select_subscribers hook. A
        completion slice runs it in its prologue, before it reads its
        target sockets, where no hook provides that event and no
        predicate rule is live (``Server._complete_staged``); with either,
        it waits for the publish's own turn in ``Server._fan_out``, after
        the rules have dropped failing members and as the hooks decide."""
        self.shared_selected = {}
        for subs in self.shared.values():
            for client, sub in subs.items():
                cls = self.shared_selected.get(client, sub)
                self.shared_selected[client] = cls.merge(sub)
                break

    def merge_shared_selected(self) -> None:
        """Fold selected shared subscribers into the non-shared set so no
        client receives duplicates (topics.go:338-347)."""
        for client, sub in self.shared_selected.items():
            cls = self.subscriptions.get(client, sub)
            self.subscriptions[client] = cls.merge(sub)


# -- the trie --------------------------------------------------------------


class _Particle:
    """One trie node (reference 'particle', topics.go:748-769).

    A particle allocates only what it holds. ``subscriptions``,
    ``shared`` and ``inline_subscriptions`` are ``None`` until the first
    entry of that kind arrives here and ``None`` again once the last one
    leaves: a path's interior particles, which hold nothing, own one
    container (their children) and no lock. A map that is not ``None``
    is never empty once the mutation that touched it is over.

    Mutations run under the trie lock, readers (``subscribers()``, the
    lock-free walks of ``ops.flat``) under none, so a map is made whole
    and filled BEFORE the particle's attribute names it and emptied
    before the attribute lets it go: a reader finds ``None`` or a map
    its own lock guards, never a half-made one."""

    __slots__ = (
        "key",
        "parent",
        "particles",
        "subscriptions",
        "shared",
        "inline_subscriptions",
        "retain_path",
    )

    def __init__(self, key: str, parent: "_Particle | None") -> None:
        self.key = key
        self.parent = parent
        self.particles: dict[str, _Particle] = {}
        self.subscriptions: Subscriptions | None = None
        self.shared: SharedSubscriptions | None = None
        self.inline_subscriptions: InlineSubscriptions | None = None
        self.retain_path = ""


class TopicsIndex:
    """A trie of topic filters with subscriber scan and retained-message
    walks (reference TopicsIndex, topics.go:350+)."""

    def __init__(self, lock_name: str = "topics_trie") -> None:
        # lock-plane adoption (mqtt_tpu.utils.locked): every host-walk
        # fallback, subscribe/unsubscribe, and retained-store mutation
        # serializes here — the prime suspect for ROADMAP item 3's
        # per-client collapse, now measured. The cluster's remote-
        # interest index passes its own name so the two tries' numbers
        # stay separable.
        from .utils.locked import InstrumentedLock

        self.retained = PacketStore(name="retained")
        self.root = _Particle("", None)
        self._lock = InstrumentedLock(lock_name, rlock=True)
        # bumped on every subscription mutation; device indexes (mqtt_tpu.ops)
        # compare against it to detect staleness
        self.version = 0
        # mutation observers: called with a Mutation under the trie lock,
        # after the version bump. The delta-staged device matcher
        # (mqtt_tpu.ops.delta) uses this to route affected topics to the
        # host walk while a stale device snapshot keeps serving everything
        # else; the mesh-sharded matcher (mqtt_tpu.parallel) additionally
        # applies the mutation to the owning shard's replica trie.
        self._observers: list[Callable[[Mutation], None]] = []
        # open bulk loads (bulk_load): above 0 a restart-sized run of
        # inserts is in progress, and a table derived from the trie now
        # would be thrown away by the next thousand entries. Moves under
        # the trie lock, so an observer reads it consistently with the
        # mutation it is handed.
        self.bulk_depth = 0
        self._bulk_end_observers: list[Callable[[], None]] = []
        # the outermost loads' open-to-close wall, summed (one clock read
        # each end): set-up's load half, as a profiler slice reads it
        self.bulk_load_seconds = 0.0
        self._bulk_t0 = 0.0
        # what the trie costs, as three plain counts that move under the
        # trie lock: live nodes (the root is one), containers alive
        # across them (children dicts and the three kinds of map), and
        # subscriptions of all three kinds the trie holds, bulk-loaded
        # ones included
        self.particles = 1
        self.particle_maps = 1
        self.held = 0

    def add_observer(
        self,
        fn: Callable[[Mutation], None],
        on_bulk_end: Optional[Callable[[], None]] = None,
    ) -> None:
        """Register a subscription-mutation observer (delta stream
        consumer). ``on_bulk_end``, when given, is called under the trie
        lock each time the last open :meth:`bulk_load` closes."""
        with self._lock:
            self._observers.append(fn)
            if on_bulk_end is not None:
                self._bulk_end_observers.append(on_bulk_end)

    def remove_observer(
        self,
        fn: Callable[[Mutation], None],
        on_bulk_end: Optional[Callable[[], None]] = None,
    ) -> None:
        with self._lock:
            if fn in self._observers:
                self._observers.remove(fn)
            if on_bulk_end in self._bulk_end_observers:
                self._bulk_end_observers.remove(on_bulk_end)

    @contextmanager
    def bulk_load(self):
        """Mark a bulk load open on this trie for the length of the block
        (``staging.bulk_register`` holds one around its whole loop, which
        is the durable restore's route; a caller that loads in several
        such calls holds one around them). Re-entrant and counted: only
        the outermost close returns ``bulk_depth`` to 0 and tells the
        observers that registered an ``on_bulk_end``, and an exception
        inside the block closes it all the same. The mutation stream is
        untouched (every entry still reaches every observer); what the
        mark changes is what an observer may put off until the close:
        the delta overlay (``mqtt_tpu.ops.delta``) rebuilds nothing
        while a load is open and builds once when it ends."""
        with self._lock:
            self.bulk_depth += 1
            if self.bulk_depth == 1:
                self._bulk_t0 = time.perf_counter()
        try:
            yield
        finally:
            with self._lock:
                self.bulk_depth -= 1
                if self.bulk_depth == 0:
                    self.bulk_load_seconds += time.perf_counter() - self._bulk_t0
                    for fn in self._bulk_end_observers:
                        # brokerlint: ok=R5 intentional in-lock delivery, as _notify: the close must be atomic with the mutation stream (per-entry recording resumes with the first mutation after it); observers are contract-bound to O(1) work (a counter and an Event.set)
                        fn()

    def _notify(self, mutation: Mutation) -> None:
        for fn in self._observers:
            # brokerlint: ok=R5 intentional in-lock delivery: the delta overlay must observe the mutation atomically with the version bump (a gap would let a stale device snapshot serve the mutated filter); the lock is an RLock, so same-thread re-registration cannot deadlock, and observers are contract-bound to be O(1) appends
            fn(mutation)

    # -- mutation ----------------------------------------------------------

    def _insert(self, client: str, subscription: Subscription) -> bool:
        """One subscription into its particle, under the trie lock; True
        if it was new. The particle's map of that kind is made here when
        this is its first entry."""
        self.version += 1
        prefix, _ = isolate_particle(subscription.filter, 0)
        if prefix.upper() == SHARE_PREFIX:
            group, _ = isolate_particle(subscription.filter, 1)
            n = self._set(subscription.filter, 2)
            shared = n.shared
            if shared is None:
                shared = SharedSubscriptions()
                existed = False
            else:
                existed = shared.get(group, client) is not None
            shared.add(group, client, subscription)
            if n.shared is None:
                n.shared = shared
                self.particle_maps += 1
        else:
            n = self._set(subscription.filter, 0)
            subs = n.subscriptions
            if subs is None:
                subs = Subscriptions()
                existed = False
            else:
                existed = subs.get(client) is not None
            subs.add(client, subscription)
            if n.subscriptions is None:
                n.subscriptions = subs
                self.particle_maps += 1
        if not existed:
            self.held += 1
        self._notify(Mutation(subscription.filter, "sub", "add", client, subscription))
        return not existed

    def subscribe(self, client: str, subscription: Subscription) -> bool:
        """Add a subscription; returns True if it was new (topics.go:401-419).
        ``$SHARE/<group>/<filter>`` roots the subtree at depth 2."""
        with self._lock:
            return self._insert(client, subscription)

    def subscribe_bulk(self, entries: list[tuple[str, Subscription]]) -> int:
        """Batched :meth:`subscribe`: one lock acquisition inserts a whole
        batch of ``(client, subscription)`` pairs — the restart
        re-registration path (ISSUE 16), where a million persisted
        subscriptions must not pay a lock round-trip (and an observer
        wake) each. Returns how many were NEW. Per-entry semantics are
        identical to :meth:`subscribe`: the version bumps and the delta
        observers fire for every entry, so device-matcher overlays see
        the same mutation stream either way."""
        added = 0
        insert = self._insert
        with self._lock:
            for client, subscription in entries:
                if insert(client, subscription):
                    added += 1
        return added

    def unsubscribe(self, filter: str, client: str) -> bool:
        """Remove a client's subscription; returns True if it existed
        (topics.go:423-448)."""
        with self._lock:
            d = 0
            prefix, _ = isolate_particle(filter, 0)
            share_sub = prefix.upper() == SHARE_PREFIX
            if share_sub:
                d = 2
            particle = self._seek(filter, d)
            if particle is None:
                return False
            self.version += 1
            if share_sub:
                group, _ = isolate_particle(filter, 1)
                shared = particle.shared
                if shared is not None and shared.get(group, client) is not None:
                    shared.delete(group, client)
                    self.held -= 1
                    if not shared.internal:
                        particle.shared = None
                        self.particle_maps -= 1
            else:
                subs = particle.subscriptions
                if subs is not None and subs.get(client) is not None:
                    subs.delete(client)
                    self.held -= 1
                    if not subs.internal:
                        particle.subscriptions = None
                        self.particle_maps -= 1
            self._trim(particle)
            self._notify(Mutation(filter, "sub", "del", client))
            return True

    def inline_subscribe(self, subscription: InlineSubscription) -> bool:
        """Add an in-process subscription keyed on its identifier; returns
        True if new (topics.go:368-378)."""
        with self._lock:
            self.version += 1
            n = self._set(subscription.filter, 0)
            inline = n.inline_subscriptions
            if inline is None:
                inline = InlineSubscriptions()
                existed = False
            else:
                existed = inline.get(subscription.identifier) is not None
            inline.add_inline(subscription)
            if n.inline_subscriptions is None:
                n.inline_subscriptions = inline
                self.particle_maps += 1
            if not existed:
                self.held += 1
            self._notify(
                Mutation(
                    subscription.filter,
                    "inline",
                    "add",
                    subscription=subscription,
                    identifier=subscription.identifier,
                )
            )
            return not existed

    def inline_subscription(self, id_: int, filter: str) -> Optional[InlineSubscription]:
        """The stored inline subscription at (identifier, filter), or
        None. The predicate plane consults it on replace/unsubscribe so
        rule refcounts track the subscription actually stored."""
        with self._lock:
            particle = self._seek(filter, 0)
            if particle is None or particle.inline_subscriptions is None:
                return None
            return particle.inline_subscriptions.get(id_)

    def inline_unsubscribe(self, id_: int, filter: str) -> bool:
        with self._lock:
            particle = self._seek(filter, 0)
            if particle is None:
                return False
            self.version += 1
            inline = particle.inline_subscriptions
            if inline is not None and inline.get(id_) is not None:
                inline.delete(id_)
                self.held -= 1
                if not inline.internal:
                    particle.inline_subscriptions = None
                    self.particle_maps -= 1
            if particle.inline_subscriptions is None:
                self._trim(particle)
            self._notify(Mutation(filter, "inline", "del", identifier=id_))
            return True

    def retain_message(self, pk: Packet) -> int:
        """Store/clear the retained message for a topic. Returns 1 when a
        message was retained, -1 when an existing one was cleared, 0 for a
        clear with nothing to clear (topics.go:453-476)."""
        with self._lock:
            n = self._set(pk.topic_name, 0)
            if pk.payload:
                n.retain_path = pk.topic_name
                self.retained.add(pk.topic_name, pk)
                return 1
            out = 0
            pke = self.retained.get(pk.topic_name)
            if pke is not None and pke.payload and pke.fixed_header.retain:
                out = -1
            n.retain_path = ""
            self.retained.delete(pk.topic_name)  # [MQTT-3.3.1-6] [MQTT-3.3.1-7]
            self._trim(n)
            return out

    def retain_bulk(self, packets: list[Packet]) -> int:
        """Batched :meth:`retain_message` for restart restore: one lock
        acquisition re-seats a whole batch of retained messages. Returns
        how many were retained (clears count like the scalar path but are
        not summed). Per-packet semantics match :meth:`retain_message`."""
        retained = 0
        with self._lock:
            for pk in packets:
                n = self._set(pk.topic_name, 0)
                if pk.payload:
                    n.retain_path = pk.topic_name
                    self.retained.add(pk.topic_name, pk)
                    retained += 1
                else:
                    n.retain_path = ""
                    self.retained.delete(pk.topic_name)
                    self._trim(n)
        return retained

    def _set(self, topic: str, d: int) -> _Particle:
        """Create (or find) the particle at a topic address (topics.go:479)."""
        parts = topic.split("/")
        n = self.root
        for key in parts[d:] if d < len(parts) else [parts[-1]]:
            p = n.particles.get(key)
            if p is None:
                p = _Particle(key, n)
                n.particles[key] = p
                self.particles += 1
                self.particle_maps += 1
            n = p
        return n

    def _seek(self, filter: str, d: int) -> _Particle | None:
        parts = filter.split("/")
        n = self.root
        for key in parts[d:] if d < len(parts) else [parts[-1]]:
            n = n.particles.get(key)
            if n is None:
                return None
        return n

    def _trim(self, n: _Particle) -> None:
        """Prune empty particles up the parent chain (topics.go:516-522)."""
        while (
            n.parent is not None
            and n.retain_path == ""
            and not n.particles
            and n.subscriptions is None
            and n.shared is None
            and n.inline_subscriptions is None
        ):
            key = n.key
            n = n.parent
            if n.particles.pop(key, None) is not None:
                self.particles -= 1
                self.particle_maps -= 1

    # -- scans -------------------------------------------------------------

    def subscribers(self, topic: str) -> Subscribers:
        """All clients subscribed to filters matching ``topic`` — THE hot
        walk the TPU matcher accelerates (topics.go:583-628). Iterative
        frontier walk (explicit stack) so deep topics cannot overflow the
        interpreter's recursion limit."""
        subs = Subscribers()
        if len(topic) == 0:
            return subs
        parts = topic.split("/")
        last = len(parts) - 1
        stack: list[tuple[_Particle, int]] = [(self.root, 0)]
        while stack:
            n, d = stack.pop()
            key = parts[d] if d < len(parts) else parts[-1]
            has_next = d < last
            for part_key in (key, "+"):
                particle = n.particles.get(part_key)
                if particle is not None:  # [MQTT-3.3.2-3]
                    if has_next:
                        stack.append((particle, d + 1))
                    else:
                        self._gather_subscriptions(topic, particle, subs)
                        self._gather_shared(topic, particle, subs)
                        self._gather_inline(topic, particle, subs)
                        wild = particle.particles.get("#")
                        if wild is not None and part_key != "+":
                            # filter/# matches filter itself, per spec 4.7.1.2
                            self._gather_subscriptions(topic, wild, subs)
                            self._gather_shared(topic, wild, subs)
                            # reference quirk (topics.go:615): gathers the
                            # parent particle's inline subs, not the wild
                            # child's
                            self._gather_inline(topic, particle, subs)
            particle = n.particles.get("#")
            if particle is not None:
                self._gather_subscriptions(topic, particle, subs)
                self._gather_shared(topic, particle, subs)
                self._gather_inline(topic, particle, subs)
        return subs

    @staticmethod
    def _ns_excluded(topic: str, filter: str) -> bool:
        """The namespace gather guards (mqtt_tpu.tenancy): a GLOBAL
        top-level-wildcard filter never reaches into a tenant namespace,
        and inside a namespace the [MQTT-4.7.1-1/2] ``$``-rule applies
        to the tenant-LOCAL first level. Zero-cost for global topics
        (one char compare)."""
        if topic[:1] != NS_CHAR or not filter:
            return False
        if filter[0] in "+#":
            return True  # global wildcard vs scoped topic
        return _ns_local0(topic) == "$" and _ns_local0(filter) in "+#"

    def _gather_subscriptions(self, topic: str, particle: _Particle, subs: Subscribers) -> None:
        """Merge a particle's subscriptions into the result set, excluding
        top-level-wildcard filters for $-topics [MQTT-4.7.1-1/2]
        (topics.go:631-648)."""
        held = particle.subscriptions
        if held is None:
            return
        for client, sub in held.get_all().items():
            if sub.filter and topic[0] == "$" and sub.filter[0] in "+#":
                continue
            if self._ns_excluded(topic, sub.filter):
                continue
            cls = subs.subscriptions.get(client, sub)
            subs.subscriptions[client] = cls.merge(sub)

    def _gather_shared(self, topic: str, particle: _Particle, subs: Subscribers) -> None:
        held = particle.shared
        if held is None:
            return
        for shares in held.get_all().values():
            for client, sub in shares.items():
                if topic[:1] == NS_CHAR:
                    # the namespace guard applies to the INNER filter
                    # (publishes match the inner topic space)
                    parts = sub.filter.split("/", 2)
                    inner = parts[2] if len(parts) > 2 else ""
                    if self._ns_excluded(topic, inner):
                        continue
                subs.shared.setdefault(sub.filter, {})[client] = sub

    def _gather_inline(self, topic: str, particle: _Particle, subs: Subscribers) -> None:
        held = particle.inline_subscriptions
        if held is None:
            return
        if topic[:1] == NS_CHAR:
            for iid, isub in held.get_all().items():
                if not self._ns_excluded(topic, isub.filter):
                    subs.inline_subscriptions[iid] = isub
            return
        subs.inline_subscriptions.update(held.get_all())

    def messages(self, filter: str) -> list[Packet]:
        """All retained messages matching ``filter`` (topics.go:525-579).
        Iterative walk — see :meth:`subscribers`."""
        pks: list[Packet] = []
        if len(filter) == 0 or len(self.retained) == 0:
            return pks
        if "#" not in filter and "+" not in filter:
            pk = self.retained.get(filter)
            if pk is not None:
                pks.append(pk)
            return pks
        parts = filter.split("/")
        last = len(parts) - 1
        # a namespace-scoped filter's local top level sits at depth 1;
        # the $SYS wildcard exclusion applies there (mqtt_tpu.tenancy)
        sys_d = 1 if parts[0][:1] == NS_CHAR else 0
        stack: list[tuple[_Particle, int]] = [(self.root, 0)]
        while stack:
            n, d = stack.pop()
            key = parts[d] if d < len(parts) else parts[-1]
            has_next = d < last
            if key in ("+", "#"):
                for adjacent in list(n.particles.values()):
                    if d == sys_d and adjacent.key == SYS_PREFIX:
                        continue
                    if d == 0 and adjacent.key[:1] == NS_CHAR:
                        # a GLOBAL wildcard never descends into a
                        # tenant namespace (scoped filters address it
                        # by its literal level instead)
                        continue
                    if not has_next and adjacent.retain_path:
                        pk = self.retained.get(adjacent.retain_path)
                        if pk is not None:
                            pks.append(pk)
                    if has_next or key == "#":
                        stack.append((adjacent, d + 1))
            else:
                particle = n.particles.get(key)
                if particle is not None:
                    if has_next:
                        stack.append((particle, d + 1))
                    elif particle.retain_path:
                        pk = self.retained.get(particle.retain_path)
                        if pk is not None:
                            pks.append(pk)
        return pks
