"""Secure multi-tenant plane (ROADMAP item 6; MQT-TZ, arxiv 2007.12442).

Three cooperating pieces turn the single-namespace broker into a
multi-tenant one whose isolation is structural, not filter-based:

- :class:`TenantPlane`: the tenant registry + CONNECT-time resolution.
  A client maps (username first, then client id — the
  ``overload_priority_users`` idiom) to a :class:`Tenant`; from then on
  every key the broker stores or matches for it — the client-registry
  id, trie filters, retained topics, $SHARE inner filters, cluster
  interest summaries — carries the tenant's namespace prefix
  (:func:`mqtt_tpu.topics.ns_scope_topic` /
  :func:`~mqtt_tpu.topics.ns_scope_filter`). Two tenants' identical
  topic strings land on disjoint trie subtrees, so cross-tenant
  delivery is impossible by construction (tests drive identical
  filter sets through wildcards, $SHARE, retained, predicates, and
  cross-worker forwards asserting zero leaks). Tenants carry a quota
  class riding the overload governor's priority-class machinery
  (PR 5): the class's weight shapes both shed and publish quotas, so a
  VIP tenant keeps publishing through a storm a bulk tenant sheds in.
  Per-tenant counters merge into the existing metrics registry as
  labeled ``mqtt_tpu_tenant_*`` families and surface per tenant under
  the tenant's OWN ``$SYS`` namespace (a tenant can only ever see its
  own broker stats) plus a global operator mirror.

- :class:`KeyRegistry`: per-(tenant, identity) AES-128 keys for the
  re-encryption stage, kept as a dense device-ready round-key table
  (``uint8 [T, 11, 16]``) so a fan-out dispatch gathers per-block keys
  on device by index.

- :class:`RecryptEngine`: MQT-TZ's broker-side re-encryption as a
  batched device kernel (:mod:`mqtt_tpu.ops.recrypt`). Publishes in a
  tenant's ``encrypted`` namespaces arrive as ``nonce || ciphertext``
  under the publisher's key; the broker decrypts once (the keystream
  dispatch rides the staged match batch — :class:`RecryptJob` travels
  through :class:`mqtt_tpu.staging.MatchStage` beside the predicate
  feature rows) and re-encrypts per subscriber with each subscriber's
  key: ONE fused keystream dispatch per fan-out tick covers every
  (publish, subscriber) block, and the XOR lands host-side off the GIL
  (numpy). The vectorized-host keystream is both the sampled
  differential oracle and the degradation target behind a
  :class:`~mqtt_tpu.resilience.CircuitBreaker` — exactly the matcher /
  predicate-engine posture (host wins on mismatch, device faults trip
  to host, the flight recorder dumps on trip).

Subscribers without a registered key receive NOTHING from an encrypted
namespace (counted, never plaintext); malformed ciphertext (shorter
than the nonce) delivers nothing and counts. Tenancy is opt-in
(``Options.tenancy``); with it off, no code path here runs.
"""

from __future__ import annotations

import logging
import os
import struct
import threading
from typing import Any, Callable, Optional

import numpy as np

from .topics import (
    NS_CHAR,
    ns_local,
    ns_scope_filter,
    ns_scope_topic,
    ns_tenant,
)

_log = logging.getLogger("mqtt_tpu.tenancy")


# -- epoch-tagged nonces (live tenant re-key, ISSUE 20) --------------------
#
# CTR ciphertext carries no authentication, so during a key rotation the
# broker cannot TELL which epoch's key sealed a payload. Rekey-aware
# clients therefore stamp the epoch into the nonce they generate: byte 0
# is a magic marker, bytes 1:3 the big-endian epoch number, bytes 3:12
# the client's own uniqueness material. The tag is only ever consulted
# for tenants that have staged an epoch (has_epochs) — tenants that
# never rotate keep the full 12 opaque bytes and none of this runs.

EPOCH_NONCE_MAGIC = 0xA7


def epoch_tag_nonce(nonce: bytes, epoch: int) -> bytes:
    """Stamp an epoch tag over a 12-byte nonce's first 3 bytes."""
    return bytes((EPOCH_NONCE_MAGIC, (epoch >> 8) & 0xFF, epoch & 0xFF)) + nonce[3:]


def nonce_epoch(nonce: bytes) -> Optional[int]:
    """The epoch a tagged nonce names, or None for an untagged nonce."""
    if len(nonce) >= 3 and nonce[0] == EPOCH_NONCE_MAGIC:
        return (nonce[1] << 8) | nonce[2]
    return None


def scope_client_id(tenant: str, client_id: str) -> str:
    """The broker-registry identity of a tenant client: scoped like a
    topic, so two tenants using the same client id can never take over
    each other's sessions (ids collide only inside one tenant)."""
    return NS_CHAR + tenant + "/" + client_id


def local_client_id(client_id: str) -> str:
    """The tenant-local client id (identity for global ids)."""
    return ns_local(client_id)


class Tenant:
    """One tenant: namespace name, quota class, encrypted prefixes, and
    the per-tenant counters ($SYS + labeled registry families). Counter
    bumps are single-writer-ish ``+=`` on the event loop — the
    telemetry.Counter posture, never a lock on the data plane."""

    __slots__ = (
        "name",
        "quota_class",
        "encrypted",
        "connected",
        "connects",
        "messages_in",
        "messages_out",
        "messages_dropped",
        "bytes_in",
        "bytes_out",
        "recrypt_fanouts",
        "max_retained",
        "max_subscriptions",
        "retained_count",
        "subscriptions_count",
        "retained_refused",
        "subscriptions_refused",
    )

    def __init__(
        self,
        name: str,
        quota_class: str = "",
        encrypted: tuple = (),
        max_retained: int = 0,
        max_subscriptions: int = 0,
    ) -> None:
        self.name = name
        self.quota_class = quota_class
        # topic-name prefixes (tenant-local) whose publishes carry the
        # nonce||ciphertext wire format and re-encrypt per subscriber
        self.encrypted = tuple(encrypted)
        self.connected = 0
        self.connects = 0
        self.messages_in = 0
        self.messages_out = 0
        self.messages_dropped = 0
        self.bytes_in = 0
        self.bytes_out = 0
        self.recrypt_fanouts = 0
        # durable COUNT caps (ISSUE 16, the MQT-TZ quota residual): how
        # many retained topics / stored subscriptions this tenant may
        # hold; 0 = unlimited (or the Options-level default cap). Counts
        # are maintained structurally at every grow/shrink site in the
        # namespaced stores; refusals answer v5 0x97 Quota exceeded.
        self.max_retained = max_retained
        self.max_subscriptions = max_subscriptions
        self.retained_count = 0
        self.subscriptions_count = 0
        self.retained_refused = 0
        self.subscriptions_refused = 0

    def is_encrypted(self, local_topic: str) -> bool:
        """Does a tenant-local topic live in an encrypted namespace?"""
        for prefix in self.encrypted:
            if local_topic.startswith(prefix):
                return True
        return False

    def sys_rows(self) -> dict:
        """The per-tenant ``$SYS/broker/tenant/*`` rows."""
        return {
            "connected": self.connected,
            "connects": self.connects,
            "messages/in": self.messages_in,
            "messages/out": self.messages_out,
            "messages/dropped": self.messages_dropped,
            "bytes/in": self.bytes_in,
            "bytes/out": self.bytes_out,
            "recrypt_fanouts": self.recrypt_fanouts,
            "retained/count": self.retained_count,
            "retained/refused": self.retained_refused,
            "subscriptions/count": self.subscriptions_count,
            "subscriptions/refused": self.subscriptions_refused,
        }


def _valid_tenant_name(name: str) -> bool:
    return bool(name) and not any(c in name for c in ("/", "+", "#", NS_CHAR))


class TenantPlane:
    """The tenant registry + CONNECT-time resolver.

    Registration happens at startup (config) or from embedder code;
    resolution runs once per CONNECT. The lock guards the registry maps
    only — scoping helpers and counter bumps are lock-free."""

    def __init__(self, registry: Optional[Any] = None) -> None:
        from .utils.locked import InstrumentedLock

        self._lock = InstrumentedLock("tenants")
        self._tenants: dict[str, Tenant] = {}
        self._users: dict[str, str] = {}  # username-or-client-id -> tenant
        self.default = ""  # tenant for unmapped clients ("" = untenanted)
        self.keys = KeyRegistry()
        self._registry = registry
        self._metered: set[str] = set()  # tenants with registered families

    # -- registration ------------------------------------------------------

    def register(
        self,
        name: str,
        quota_class: str = "",
        encrypted: tuple = (),
    ) -> Tenant:
        """Create (or return) one tenant. Invalid names raise — tenancy
        is operator config, not wire input, so a typo fails loudly at
        startup instead of silently splitting a namespace."""
        if not _valid_tenant_name(name):
            raise ValueError(f"invalid tenant name: {name!r}")
        with self._lock:
            t = self._tenants.get(name)
            if t is None:
                t = self._tenants[name] = Tenant(
                    name, quota_class=quota_class, encrypted=tuple(encrypted)
                )
            return t

    def map_user(self, ident: str, tenant: str) -> None:
        """Route a username-or-client-id to a tenant at CONNECT."""
        with self._lock:
            self._users[ident] = tenant

    def configure(
        self,
        tenants: Optional[dict],
        users: Optional[dict],
        default: str = "",
    ) -> None:
        """Load the Options/config-file maps: ``tenants`` is
        name -> {quota_class, encrypted: [prefix...], keys: {ident: hex}},
        ``users`` is username-or-client-id -> tenant name."""
        for name, cfg in (tenants or {}).items():
            cfg = cfg or {}
            t = self.register(
                str(name),
                quota_class=str(cfg.get("quota_class", "") or ""),
                encrypted=tuple(cfg.get("encrypted", ()) or ()),
            )
            # per-tenant count-cap overrides (fall back to the
            # Options-level tenant_max_* defaults when absent)
            try:
                t.max_retained = int(cfg.get("max_retained", t.max_retained))
                t.max_subscriptions = int(
                    cfg.get("max_subscriptions", t.max_subscriptions)
                )
            except (TypeError, ValueError):
                _log.warning(
                    "tenant %r max_retained/max_subscriptions is not an "
                    "integer; cap ignored",
                    t.name,
                )
            for ident, hexkey in (cfg.get("keys") or {}).items():
                try:
                    key = bytes.fromhex(str(hexkey))
                    self.keys.set_key(t.name, str(ident), key)
                except ValueError:
                    _log.warning(
                        "tenant %r key for %r is not a 32-hex-char "
                        "AES-128 key; ignored",
                        t.name,
                        ident,
                    )
        for ident, tenant in (users or {}).items():
            self.map_user(str(ident), str(tenant))
        if default:
            self.register(str(default))
            self.default = str(default)

    # -- resolution --------------------------------------------------------

    def resolve(self, username: str, client_id: str) -> Optional[Tenant]:
        """The CONNECT-time tenant verdict: username first, then client
        id, then the default tenant; None = untenanted (global
        namespace). An unregistered tenant NAME in the user map
        auto-registers — the mapping is the operator's intent."""
        with self._lock:
            name = (
                self._users.get(username)
                or self._users.get(client_id)
                or self.default
            )
            if not name:
                return None
            t = self._tenants.get(name)
        if t is None:
            t = self.register(name)
        return t

    def get(self, name: str) -> Optional[Tenant]:
        with self._lock:
            return self._tenants.get(name)

    def tenant_of_topic(self, scoped_topic: str) -> Optional[Tenant]:
        """The tenant owning a scoped topic key (None for global)."""
        name = ns_tenant(scoped_topic)
        if not name:
            return None
        with self._lock:
            return self._tenants.get(name)

    def __len__(self) -> int:
        with self._lock:
            return len(self._tenants)

    # -- scoping (module-level helpers re-exported for call sites) ---------

    scope_topic = staticmethod(ns_scope_topic)
    scope_filter = staticmethod(ns_scope_filter)
    local = staticmethod(ns_local)

    # -- accounting --------------------------------------------------------

    def note_connect(self, tenant: Tenant) -> None:
        tenant.connects += 1
        tenant.connected += 1
        if self._registry is not None and tenant.name not in self._metered:
            # lazy per-tenant families: registered at FIRST connect, off
            # the plane lock (the registry takes its own), so 1k
            # registered-but-idle tenants cost the scrape nothing
            with self._lock:
                fresh = tenant.name not in self._metered
                self._metered.add(tenant.name)
            if fresh:
                self._register_tenant_metrics(tenant)

    def note_disconnect(self, tenant: Tenant) -> None:
        tenant.connected = max(0, tenant.connected - 1)

    def active_tenants(self) -> list[Tenant]:
        """Tenants with live connections OR traffic history — the set
        the per-tenant $SYS tick publishes for (bounded by activity,
        never by the registered-tenant count)."""
        with self._lock:
            snap = list(self._tenants.values())
        return [t for t in snap if t.connected > 0 or t.connects > 0]

    def _register_tenant_metrics(self, tenant: Tenant) -> None:
        r = self._registry
        for name, attr in (
            ("mqtt_tpu_tenant_messages_in_total", "messages_in"),
            ("mqtt_tpu_tenant_messages_out_total", "messages_out"),
            ("mqtt_tpu_tenant_messages_dropped_total", "messages_dropped"),
            ("mqtt_tpu_tenant_bytes_in_total", "bytes_in"),
            ("mqtt_tpu_tenant_bytes_out_total", "bytes_out"),
            ("mqtt_tpu_tenant_connects_total", "connects"),
            ("mqtt_tpu_tenant_retained_refused_total", "retained_refused"),
            (
                "mqtt_tpu_tenant_subscriptions_refused_total",
                "subscriptions_refused",
            ),
        ):
            r.counter(
                name,
                f"Per-tenant Tenant.{attr}",
                fn=lambda t=tenant, a=attr: getattr(t, a),
                tenant=tenant.name,
            )
        r.gauge(
            "mqtt_tpu_tenant_connected",
            "Live connections per tenant",
            fn=lambda t=tenant: t.connected,
            tenant=tenant.name,
        )
        r.gauge(
            "mqtt_tpu_tenant_retained_count",
            "Retained topics currently held per tenant (count-capped by "
            "max_retained / tenant_max_retained)",
            fn=lambda t=tenant: t.retained_count,
            tenant=tenant.name,
        )
        r.gauge(
            "mqtt_tpu_tenant_subscriptions_count",
            "Stored subscriptions currently held per tenant (count-capped "
            "by max_subscriptions / tenant_max_subscriptions)",
            fn=lambda t=tenant: t.subscriptions_count,
            tenant=tenant.name,
        )


class KeyRegistry:
    """Per-(tenant, identity) AES-128 keys, expanded once into a dense
    device-ready round-key table. Identity is a tenant-LOCAL client id
    or username — whatever the operator keyed the config on.

    Live re-key (ISSUE 20) layers EPOCHS on top without disturbing the
    dense-id contract: ``stage_epoch`` registers a tenant's next key
    generation as FRESH table rows (current lookups untouched — sealing
    stays on the old keys while the new ones distribute),
    ``activate_epoch`` atomically flips the tenant's current-id map to
    the staged rows (old rows stay addressable by epoch for the
    in-flight drain), and ``retire_epoch`` cuts the old generation off:
    epoch-tagged lookups below the retirement floor answer -2 and the
    retired round-key rows are scrubbed to zeros so not even a buggy
    path can seal with the dead key bits. Fan-out ticks snapshot
    ``table()`` before dispatch, so in-flight work keyed pre-rotation
    drains on the old key material regardless."""

    def __init__(self) -> None:
        from .utils.locked import InstrumentedLock

        self._lock = InstrumentedLock("recrypt_keys")
        self._ids: dict[tuple[str, str], int] = {}
        self._round_keys: list[np.ndarray] = []  # [11, 16] per key id
        self._table: Optional[np.ndarray] = None  # stacked cache
        # re-key epochs (ISSUE 20): tenant -> current epoch (absent = 0),
        # (tenant, ident, epoch) -> kid, tenant -> staged-but-inactive
        # epoch, tenant -> lowest still-live epoch (retirement floor)
        self._epochs: dict[str, int] = {}
        self._epoch_kids: dict[tuple[str, str, int], int] = {}
        self._staged: dict[str, int] = {}
        self._floor: dict[str, int] = {}

    def set_key(self, tenant: str, ident: str, key: bytes) -> int:
        """Register (or rotate) one identity's key; returns its dense id."""
        from .ops.recrypt import expand_key

        rk = expand_key(key)  # raises on a non-16-byte key
        with self._lock:
            kid = self._ids.get((tenant, ident))
            if kid is None:
                kid = len(self._round_keys)
                self._ids[(tenant, ident)] = kid
                self._round_keys.append(rk)
            else:
                self._round_keys[kid] = rk
            self._epoch_kids[(tenant, ident, self._epochs.get(tenant, 0))] = kid
            self._table = None  # rebuilt on next snapshot
            return kid

    # -- re-key epochs (ISSUE 20) ------------------------------------------

    def stage_epoch(self, tenant: str, keys: dict) -> int:
        """Register a tenant's NEXT key generation (ident -> raw key)
        as fresh table rows; current lookups keep resolving the old
        generation until :meth:`activate_epoch`. Returns the staged
        epoch number."""
        from .ops.recrypt import expand_key

        rks = {ident: expand_key(key) for ident, key in keys.items()}
        with self._lock:
            epoch = self._epochs.get(tenant, 0) + 1
            for ident, rk in rks.items():
                kid = len(self._round_keys)
                self._round_keys.append(rk)
                self._epoch_kids[(tenant, ident, epoch)] = kid
            self._staged[tenant] = epoch
            self._table = None
            return epoch

    def activate_epoch(self, tenant: str) -> int:
        """Flip the tenant's current-id map to the staged generation
        (sealing switches atomically); the old generation stays
        addressable by epoch tag for the in-flight drain. Returns the
        now-current epoch (no-op -1 when nothing is staged)."""
        with self._lock:
            epoch = self._staged.pop(tenant, -1)
            if epoch < 0:
                return -1
            for (t, ident, ep), kid in self._epoch_kids.items():
                if t == tenant and ep == epoch:
                    self._ids[(tenant, ident)] = kid
            self._epochs[tenant] = epoch
            return epoch

    def retire_epoch(self, tenant: str, epoch: int) -> int:
        """Retire every generation of a tenant up to and including
        ``epoch``: tagged lookups below the new floor answer -2
        (stale), and the retired round-key rows are scrubbed to zeros.
        Returns how many rows were scrubbed."""
        scrubbed = 0
        with self._lock:
            floor = max(self._floor.get(tenant, 0), epoch + 1)
            current = self._epochs.get(tenant, 0)
            floor = min(floor, current)  # never retire the live epoch
            self._floor[tenant] = floor
            live = set(self._ids.values())
            for (t, _ident, ep), kid in self._epoch_kids.items():
                if t == tenant and ep < floor and kid not in live:
                    if self._round_keys[kid].any():
                        self._round_keys[kid] = np.zeros((11, 16), np.uint8)
                        scrubbed += 1
            if scrubbed:
                self._table = None
        return scrubbed

    def current_epoch(self, tenant: str) -> int:
        with self._lock:
            return self._epochs.get(tenant, 0)

    def staged_epoch(self, tenant: str) -> int:
        """The staged-but-inactive epoch, or -1."""
        with self._lock:
            return self._staged.get(tenant, -1)

    def has_epochs(self, tenant: str) -> bool:
        """Has this tenant ever staged a re-key? (Gates all epoch-tag
        nonce interpretation — tenants that never rotate keep the full
        12 opaque nonce bytes.)"""
        with self._lock:
            return (
                self._epochs.get(tenant, 0) > 0 or tenant in self._staged
            )

    def kid_for_epoch(self, tenant: str, ident: str, epoch: int) -> int:
        """The dense key id of one identity AT one epoch: -1 = no such
        key, -2 = that generation is retired (stale)."""
        with self._lock:
            if epoch < self._floor.get(tenant, 0):
                return -2
            kid = self._epoch_kids.get((tenant, ident, epoch))
            if kid is not None:
                return kid
            # identities keyed before the first rotation live at epoch
            # 0 in _ids only
            if epoch == 0:
                return self._ids.get((tenant, ident), -1)
            return -1

    def key_id(self, tenant: str, ident: str) -> int:
        """The dense key id for an identity, or -1 (no key registered)."""
        with self._lock:
            return self._ids.get((tenant, ident), -1)

    def key_ids(self, tenant: str, idents_list: list) -> list:
        """Batch lookup for a fan-out tick: one lock round trip for the
        whole target list. Each element of ``idents_list`` is a tuple of
        candidate identities; the first registered one wins (-1 = none)."""
        return self.key_ids_with_epoch(tenant, idents_list)[0]

    def key_ids_with_epoch(
        self, tenant: str, idents_list: list
    ) -> tuple[list, int]:
        """:meth:`key_ids` plus the tenant's current epoch, resolved in
        the SAME lock round trip — a fan-out tick racing an
        ``activate_epoch`` must never stamp new-epoch nonce tags onto
        old-generation key ids (or vice versa)."""
        with self._lock:
            ids = self._ids
            out = []
            for idents in idents_list:
                kid = -1
                for ident in idents:
                    if ident:
                        kid = ids.get((tenant, ident), -1)
                        if kid >= 0:
                            break
                out.append(kid)
            return out, self._epochs.get(tenant, 0)

    def table(self) -> Optional[np.ndarray]:
        """The stacked round-key table ``uint8 [T, 11, 16]`` (None when
        no keys exist); cached until the next mutation."""
        with self._lock:
            if self._table is None and self._round_keys:
                self._table = np.stack(self._round_keys)
            return self._table

    def __len__(self) -> int:
        with self._lock:
            return len(self._ids)


class RecryptJob:
    """One publish's decrypt leg through the staged pipeline: built at
    submit time (mqtt_tpu.server), its keystream dispatch rides the
    match batch's issue/sync legs (mqtt_tpu.staging), and the fan-out
    path XORs the attached keystream — or falls back to the host path
    when the batch never touched the device."""

    __slots__ = ("key_id", "nonce", "n_blocks", "keystream", "error")

    def __init__(
        self, key_id: int, nonce: bytes, n_blocks: int, error: str = ""
    ) -> None:
        self.key_id = key_id
        self.nonce = nonce
        self.n_blocks = n_blocks
        self.keystream: Optional[np.ndarray] = None  # uint8 [n_blocks, 16]
        self.error = error  # "no_key" | "malformed" | "" (viable)


class RecryptEngine:
    """Batched per-subscriber payload re-encryption with host oracle +
    breaker degradation (the matcher/predicate-engine resilience
    posture, applied to crypto)."""

    def __init__(
        self,
        keys: KeyRegistry,
        oracle_sample: int = 64,
        breaker: Optional[Any] = None,
        registry: Optional[Any] = None,
        device_min_blocks: int = 4,
    ) -> None:
        from .ops.recrypt import NONCE_BYTES

        self.keys = keys
        self.nonce_bytes = NONCE_BYTES
        self.oracle_sample = max(0, oracle_sample)
        # a dispatch below this many keystream blocks runs on the host
        # outright: the samples are host-resident, so a tiny batch's
        # device round trip only adds link latency (the predicate
        # engine's device_agg_min_batch posture)
        self.device_min_blocks = max(1, device_min_blocks)
        self._device_enabled = True
        if breaker is None:
            from .resilience import CircuitBreaker

            breaker = CircuitBreaker(failure_threshold=3)
        self.breaker = breaker
        # nonce source: a 6-byte random base per engine lifetime + a
        # 6-byte big-endian counter (12 bytes total). The counter gives
        # uniqueness within one lifetime (2^48 re-encryptions); the
        # 48-bit random base keeps distinct lifetimes (restarts, other
        # workers) from colliding under the same persistent subscriber
        # keys — CTR nonce reuse under one key leaks plaintext XORs, so
        # the base is the cross-restart guard. Tests may seed via
        # reseed_nonce() for reproducible wires.
        self._nonce_base = os.urandom(6)
        self._nonce_ctr = 0
        self._nonce_lock = threading.Lock()
        # counters ($SYS/broker/recrypt/* + mqtt_tpu_recrypt_*)
        self.fanouts = 0  # publishes re-encrypted per subscriber
        self.device_batches = 0
        self.device_blocks = 0
        self.host_blocks = 0
        self.device_errors = 0
        self.oracle_checks = 0
        self.oracle_mismatches = 0
        self.no_key_drops = 0  # deliveries withheld: subscriber keyless
        self.malformed = 0  # publishes dropped: bad ciphertext framing
        # re-key epoch counters (ISSUE 20, mqtt_tpu_recrypt_epoch_*)
        self.rekeys = 0  # epoch rotations completed (activate)
        self.resealed = 0  # retained payloads re-sealed across epochs
        self.stale_epoch_drops = 0  # publishes under a RETIRED epoch key
        self._dispatch_seq = 0  # oracle sampling clock
        self._registry = registry
        self._epoch_metered: set[str] = set()
        if registry is not None:
            self._register_metrics(registry)

    # -- knobs -------------------------------------------------------------

    def set_device_enabled(self, enabled: bool) -> None:
        self._device_enabled = enabled

    def reseed_nonce(self, base: bytes, ctr: int = 0) -> None:
        """Pin the nonce stream (tests / differential replays)."""
        with self._nonce_lock:
            self._nonce_base = base[:6].ljust(6, b"\x00")
            self._nonce_ctr = ctr

    def next_nonce(self) -> bytes:
        with self._nonce_lock:
            self._nonce_ctr += 1
            ctr = self._nonce_ctr
        return self._nonce_base + struct.pack(">Q", ctr)[2:]

    def _next_nonces(self, n: int) -> np.ndarray:
        """``n`` fresh 12-byte nonces as uint8 [n, 12] — one lock round
        trip and one vectorized fill for a whole fan-out tick."""
        with self._nonce_lock:
            start = self._nonce_ctr + 1
            self._nonce_ctr += n
        out = np.empty((n, 12), dtype=np.uint8)
        out[:, :6] = np.frombuffer(self._nonce_base, dtype=np.uint8)
        ctrs = (start + np.arange(n, dtype=np.uint64)).astype(">u8")
        out[:, 6:] = ctrs.view(np.uint8).reshape(n, 8)[:, 2:]
        return out

    # -- job construction (server submit path) -----------------------------

    def decrypt_job(
        self, tenant: Tenant, idents: tuple, payload: bytes
    ) -> RecryptJob:
        """The publisher-side decrypt job for one encrypted-namespace
        publish. ``idents`` are the candidate key identities (local
        client id, then username). A keyless publisher or malformed
        framing yields an errored job — the fan-out drops the publish
        (counted), never delivers ciphertext it cannot re-key."""
        if len(payload) < self.nonce_bytes:
            self.malformed += 1
            return RecryptJob(-1, b"", 0, error="malformed")
        # epoch-tagged nonce (ISSUE 20): for a tenant mid/post-rotation
        # the tag names WHICH generation sealed this payload — old-epoch
        # publishes keep decrypting through the drain, retired epochs
        # drop (counted), untagged nonces resolve the current generation
        epoch = None
        if self.keys.has_epochs(tenant.name):
            epoch = nonce_epoch(payload[: self.nonce_bytes])
        kid = -1
        for ident in idents:
            if not ident:
                continue
            if epoch is None:
                kid = self.keys.key_id(tenant.name, ident)
            else:
                kid = self.keys.kid_for_epoch(tenant.name, ident, epoch)
                if kid == -2:
                    self.stale_epoch_drops += 1
                    return RecryptJob(-1, b"", 0, error="stale_epoch")
            if kid >= 0:
                break
        if kid < 0:
            self.no_key_drops += 1
            return RecryptJob(-1, b"", 0, error="no_key")
        nonce = payload[: self.nonce_bytes]
        n_blocks = (len(payload) - self.nonce_bytes + 15) // 16
        return RecryptJob(kid, nonce, n_blocks)

    # -- staged decrypt leg (rides MatchStage) -----------------------------

    def issue_batch(self, jobs: list) -> Optional[Callable]:
        """Issue ONE device keystream dispatch covering every viable
        decrypt job in a staged batch; returns a zero-arg resolver (run
        in the drain loop's executor leg beside the match sync) or None
        when the device path is unavailable. Mirrors
        ``PredicateEngine.eval_batch_async`` — the resolver never
        raises; failures land on the breaker and the host path serves."""
        viable = [
            j
            for j in jobs
            if j is not None and not j.error and j.n_blocks > 0
        ]
        if not viable or not self._device_enabled:
            return None
        total = sum(j.n_blocks for j in viable)
        if total < self.device_min_blocks:
            return None
        table = self.keys.table()
        if table is None:
            return None
        breaker = self.breaker
        probing = False
        if not breaker.allow():
            if not breaker.acquire_probe():
                return None  # degraded: host keystream serves this batch
            probing = True
        try:
            from .ops.recrypt import ctr_counters, keystream_async

            kidx = np.empty(total, dtype=np.int32)
            counters = np.empty((total, 16), dtype=np.uint8)
            spans = []
            off = 0
            for j in viable:
                kidx[off : off + j.n_blocks] = j.key_id
                counters[off : off + j.n_blocks] = ctr_counters(
                    j.nonce, j.n_blocks
                )
                spans.append((j, off, off + j.n_blocks))
                off += j.n_blocks
            resolver = keystream_async(table, kidx, counters)
        except Exception:
            _log.exception("recrypt device issue failed; host path")
            self.device_errors += 1
            if probing:
                breaker.record_probe_failure("issue")
            else:
                breaker.record_failure("issue")
            return None

        def resolve() -> Optional[list]:
            try:
                rows = resolver()
            except Exception:
                _log.exception("recrypt device resolve failed; host path")
                self.device_errors += 1
                if probing:
                    self.breaker.record_probe_failure("resolve")
                else:
                    self.breaker.record_failure("resolve")
                return None
            if probing:
                self.breaker.record_probe_success()
            else:
                self.breaker.record_success()
            self.device_batches += 1
            self.device_blocks += total
            self._maybe_oracle(table, kidx, counters, rows)
            return [(j, rows[a:b]) for j, a, b in spans]

        return resolve

    @staticmethod
    def attach(resolved: Optional[list]) -> None:
        """Stamp resolved keystream slices onto their jobs (drain loop,
        before futures complete)."""
        if resolved is None:
            return
        for job, rows in resolved:
            job.keystream = rows

    def _maybe_oracle(
        self,
        table: np.ndarray,
        kidx: np.ndarray,
        counters: np.ndarray,
        rows: np.ndarray,
    ) -> None:
        """The sampled differential: 1-in-N device dispatches re-derive
        the whole batch on the vectorized host path and compare
        bit-for-bit. AES is deterministic, so the tolerance is zero; a
        mismatch means a broken kernel/transfer and the HOST result is
        ground truth — but keystream rows are already attached by the
        caller, so the mismatch path recomputes per-job host keystreams
        at apply time by clearing the device rows."""
        self._dispatch_seq += 1
        if (
            self.oracle_sample <= 0
            or self._dispatch_seq % self.oracle_sample
        ):
            return
        from .ops.recrypt import host_keystream

        self.oracle_checks += 1
        want = host_keystream(table, kidx, counters)
        if not np.array_equal(want, rows):
            self.oracle_mismatches += 1
            _log.warning(
                "recrypt oracle mismatch: device keystream differs from "
                "host over %d blocks; host wins",
                len(kidx),
            )
            rows[:] = want  # host is ground truth

    # -- apply (fan-out path) ----------------------------------------------

    def _host_keystream_for(
        self, key_id: int, nonce: bytes, n_blocks: int
    ) -> np.ndarray:
        from .ops.recrypt import ctr_counters, host_keystream

        table = self.keys.table()
        assert table is not None  # caller resolved key_id from it
        self.host_blocks += n_blocks
        return host_keystream(
            table,
            np.full(n_blocks, key_id, dtype=np.int32),
            ctr_counters(nonce, n_blocks),
        )

    def open_publish(
        self,
        tenant: Tenant,
        idents: tuple,
        payload: bytes,
        job: Optional[RecryptJob] = None,
    ) -> Optional[bytes]:
        """The publish's plaintext, from the staged job's attached
        keystream when the batch rode the device, else the host path.
        None = undeliverable (keyless publisher / malformed framing) —
        the fan-out drops the publish, counted."""
        if job is None:
            job = self.decrypt_job(tenant, idents, payload)
        if job.error:
            return None
        from .ops.recrypt import xor_into

        ks = job.keystream
        if ks is None:
            ks = self._host_keystream_for(job.key_id, job.nonce, job.n_blocks)
        return xor_into(payload[self.nonce_bytes :], ks)

    def seal_fanout_raw(
        self, tenant: Tenant, plaintext: bytes, targets: list
    ) -> tuple:
        """The batched keystream half of :meth:`seal_fanout`: ONE
        keystream generation for every keyed target (device when the
        batch is worth a dispatch and the breaker admits it; vectorized
        host otherwise), WITHOUT the per-target ciphertext assembly.
        Returns ``(keyed, nonces, rows)`` — ``keyed`` the [(target_key,
        key_id), ...] that resolved a key (aligned with ``nonces``
        uint8 [J, 12] and ``rows`` uint8 [J*n_blocks, 16]; ``rows`` is
        None for zero-length plaintexts) — or None when no target is
        keyed. The zero-materialization fan-out consumes this directly
        and assembles per-subscriber frames from the shared keystream
        XOR in C (native.assemble_frames); keyless targets are counted
        and absent from ``keyed``."""
        from .ops.recrypt import keystream_async

        n_blocks = (len(plaintext) + 15) // 16
        kids, epoch = self.keys.key_ids_with_epoch(
            tenant.name, [t[1] for t in targets]
        )
        keyed = [(t[0], kid) for t, kid in zip(targets, kids) if kid >= 0]
        dropped = len(targets) - len(keyed)
        if dropped:
            self.no_key_drops += dropped
        if not keyed:
            return None
        self.fanouts += 1
        tenant.recrypt_fanouts += 1
        j = len(keyed)
        nonces = self._next_nonces(j)  # uint8 [J, 12]
        if epoch > 0:
            # post-rotation tenants get epoch-tagged subscriber nonces:
            # a subscriber holding both generations through the drain
            # picks its key off the tag instead of trial-decrypting
            nonces[:, 0] = EPOCH_NONCE_MAGIC
            nonces[:, 1] = (epoch >> 8) & 0xFF
            nonces[:, 2] = epoch & 0xFF
        if n_blocks == 0:
            # zero-length plaintext: the wire payload is the bare nonce
            return keyed, nonces, None
        total = n_blocks * j
        table = self.keys.table()
        # one vectorized counter build for the whole tick: each job's
        # blocks repeat its nonce and count 0..n_blocks-1 big-endian
        kidx = np.repeat(
            np.array([kid for _t, kid in keyed], dtype=np.int32), n_blocks
        )
        counters = np.empty((total, 16), dtype=np.uint8)
        counters[:, :12] = np.repeat(nonces, n_blocks, axis=0)
        ctr = np.tile(
            np.arange(n_blocks, dtype=np.uint32).astype(">u4"), j
        )
        counters[:, 12:] = ctr.view(np.uint8).reshape(total, 4)
        rows = None
        if (
            self._device_enabled
            and total >= self.device_min_blocks
            and self.breaker.allow()
        ):
            try:
                rows = keystream_async(table, kidx, counters)()
                self.breaker.record_success()
                self.device_batches += 1
                self.device_blocks += total
                self._maybe_oracle(table, kidx, counters, rows)
            except Exception:
                _log.exception("recrypt fan-out dispatch failed; host path")
                self.device_errors += 1
                self.breaker.record_failure("fanout")
                rows = None
        if rows is None:
            from .ops.recrypt import host_keystream

            self.host_blocks += total
            rows = host_keystream(table, kidx, counters)
        return keyed, nonces, rows

    def seal_fanout(
        self, tenant: Tenant, plaintext: bytes, targets: list
    ) -> dict:
        """Re-encrypt one plaintext for every keyed target in ONE
        batched keystream generation (device when the batch is worth a
        dispatch and the breaker admits it; vectorized host otherwise).
        ``targets`` yield (target_key, idents) where ``idents`` are the
        key-identity candidates; returns target_key ->
        ``nonce || ciphertext`` for keyed targets only (keyless targets
        are counted and withheld)."""
        out: dict = {}
        raw = self.seal_fanout_raw(tenant, plaintext, targets)
        if raw is None:
            return out
        keyed, nonces, rows = raw
        if rows is None:
            for i, (tkey, _kid) in enumerate(keyed):
                out[tkey] = nonces[i].tobytes()
            return out
        # one vectorized XOR for the whole tick, then per-target slices
        j = len(keyed)
        n_blocks = (len(plaintext) + 15) // 16
        pt = np.frombuffer(plaintext, dtype=np.uint8)
        ct = (
            rows.reshape(j, n_blocks * 16)[:, : len(plaintext)] ^ pt[None, :]
        )
        for i, (tkey, _kid) in enumerate(keyed):
            out[tkey] = nonces[i].tobytes() + ct[i].tobytes()
        return out

    # -- re-key re-seal (ISSUE 20) -----------------------------------------

    def reseal_batch(
        self, tenant: Tenant, items: list, epoch: int
    ) -> list:
        """Re-seal a batch of stored ciphertexts across a key rotation
        in ONE batched keystream dispatch: every item's decrypt blocks
        (old generation) and seal blocks (new generation) land in the
        SAME device call, then one XOR pass per item rewrites the
        ciphertext — the MQT-TZ re-encryption shape applied to the
        retained store. ``items`` yield ``(payload, old_kid, new_kid)``
        (payload = ``nonce || ciphertext``); returns the new payloads
        (epoch-tagged nonce || ciphertext), None per malformed item."""
        from .ops.recrypt import ctr_counters, keystream_async

        nb = self.nonce_bytes
        spans = []  # (idx, ct, old_off, n_blocks)
        out: list = [None] * len(items)
        total = 0
        for i, (payload, old_kid, new_kid) in enumerate(items):
            if len(payload) < nb or old_kid < 0 or new_kid < 0:
                continue
            ct = payload[nb:]
            n = (len(ct) + 15) // 16
            spans.append((i, payload[:nb], ct, total, n))
            total += n
        if not spans:
            return out
        fresh = self._next_nonces(len(spans))
        fresh[:, 0] = EPOCH_NONCE_MAGIC
        fresh[:, 1] = (epoch >> 8) & 0xFF
        fresh[:, 2] = epoch & 0xFF
        # combined dispatch: [decrypt blocks | seal blocks]
        kidx = np.empty(2 * total, dtype=np.int32)
        counters = np.empty((2 * total, 16), dtype=np.uint8)
        for s, (i, old_nonce, ct, off, n) in enumerate(spans):
            _payload, old_kid, new_kid = items[i]
            kidx[off : off + n] = old_kid
            counters[off : off + n] = ctr_counters(old_nonce, n)
            kidx[total + off : total + off + n] = new_kid
            counters[total + off : total + off + n] = ctr_counters(
                fresh[s].tobytes(), n
            )
        table = self.keys.table()
        rows = None
        if (
            self._device_enabled
            and 2 * total >= self.device_min_blocks
            and table is not None
            and self.breaker.allow()
        ):
            try:
                rows = keystream_async(table, kidx, counters)()
                self.breaker.record_success()
                self.device_batches += 1
                self.device_blocks += 2 * total
                self._maybe_oracle(table, kidx, counters, rows)
            except Exception:
                _log.exception("recrypt re-seal dispatch failed; host path")
                self.device_errors += 1
                self.breaker.record_failure("reseal")
                rows = None
        if rows is None:
            from .ops.recrypt import host_keystream

            assert table is not None  # caller resolved both kids from it
            self.host_blocks += 2 * total
            rows = host_keystream(table, kidx, counters)
        for s, (i, _old_nonce, ct, off, n) in enumerate(spans):
            if n == 0:
                out[i] = fresh[s].tobytes()
                self.resealed += 1
                continue
            c = np.frombuffer(ct, dtype=np.uint8)
            ks_old = rows[off : off + n].reshape(-1)[: len(ct)]
            ks_new = rows[total + off : total + off + n].reshape(-1)[: len(ct)]
            out[i] = fresh[s].tobytes() + (c ^ ks_old ^ ks_new).tobytes()
            self.resealed += 1
        return out

    def note_rekey(self, tenant: str) -> None:
        """Account one completed rotation and lazily register the
        per-tenant epoch gauge (mqtt_tpu_recrypt_epoch)."""
        self.rekeys += 1
        r = self._registry
        if r is not None and tenant not in self._epoch_metered:
            self._epoch_metered.add(tenant)
            r.gauge(
                "mqtt_tpu_recrypt_epoch",
                "Current re-key epoch per tenant (0 = never rotated)",
                fn=lambda t=tenant: self.keys.current_epoch(t),
                tenant=tenant,
            )

    # -- client-side helpers (tests, embedders) ----------------------------

    def seal_with_key(
        self, key: bytes, plaintext: bytes, nonce: Optional[bytes] = None
    ) -> bytes:
        """Encrypt ``plaintext`` under a raw key — what a publishing
        CLIENT does before the wire (and what tests use to fabricate
        encrypted publishes)."""
        from .ops.recrypt import (
            aes_encrypt_blocks,
            ctr_counters,
            expand_key,
            xor_into,
        )

        nonce = nonce if nonce is not None else self.next_nonce()
        n_blocks = (len(plaintext) + 15) // 16
        if n_blocks == 0:
            return nonce
        rk = expand_key(key)
        ks = aes_encrypt_blocks(
            np.broadcast_to(rk, (n_blocks, 11, 16)),
            ctr_counters(nonce, n_blocks),
        )
        return nonce + xor_into(plaintext, ks)

    def open_with_key(self, key: bytes, payload: bytes) -> bytes:
        """Decrypt a ``nonce || ciphertext`` wire payload under a raw
        key — what a subscribing CLIENT does."""
        from .ops.recrypt import (
            aes_encrypt_blocks,
            ctr_counters,
            expand_key,
            xor_into,
        )

        nonce, ct = payload[: self.nonce_bytes], payload[self.nonce_bytes :]
        n_blocks = (len(ct) + 15) // 16
        if n_blocks == 0:
            return b""
        rk = expand_key(key)
        ks = aes_encrypt_blocks(
            np.broadcast_to(rk, (n_blocks, 11, 16)),
            ctr_counters(nonce, n_blocks),
        )
        return xor_into(ct, ks)

    # -- observability -----------------------------------------------------

    def gauges(self) -> dict:
        """The $SYS/broker/recrypt/* tree."""
        return {
            "keys": len(self.keys),
            "fanouts": self.fanouts,
            "device_batches": self.device_batches,
            "device_blocks": self.device_blocks,
            "host_blocks": self.host_blocks,
            "device_errors": self.device_errors,
            "oracle_checks": self.oracle_checks,
            "oracle_mismatches": self.oracle_mismatches,
            "no_key_drops": self.no_key_drops,
            "malformed": self.malformed,
            "rekeys": self.rekeys,
            "resealed": self.resealed,
            "stale_epoch_drops": self.stale_epoch_drops,
            "breaker_state": self.breaker.state,
        }

    def _register_metrics(self, registry: Any) -> None:
        registry.gauge(
            "mqtt_tpu_recrypt_keys",
            "Registered per-(tenant, identity) AES keys",
            fn=lambda: len(self.keys),
        )
        for name, attr in (
            ("mqtt_tpu_recrypt_fanouts_total", "fanouts"),
            ("mqtt_tpu_recrypt_device_batches_total", "device_batches"),
            ("mqtt_tpu_recrypt_device_blocks_total", "device_blocks"),
            ("mqtt_tpu_recrypt_host_blocks_total", "host_blocks"),
            ("mqtt_tpu_recrypt_device_errors_total", "device_errors"),
            ("mqtt_tpu_recrypt_oracle_checks_total", "oracle_checks"),
            ("mqtt_tpu_recrypt_oracle_mismatches_total", "oracle_mismatches"),
            ("mqtt_tpu_recrypt_no_key_drops_total", "no_key_drops"),
            ("mqtt_tpu_recrypt_malformed_total", "malformed"),
            ("mqtt_tpu_recrypt_epoch_rekeys_total", "rekeys"),
            ("mqtt_tpu_recrypt_epoch_resealed_total", "resealed"),
            ("mqtt_tpu_recrypt_epoch_stale_drops_total", "stale_epoch_drops"),
        ):
            registry.counter(
                name,
                f"RecryptEngine.{attr}",
                fn=lambda a=attr: getattr(self, a),
            )
