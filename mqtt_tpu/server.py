"""The broker server: lifecycle, CONNECT handshake, packet dispatch, QoS
flows, retained/LWT/$SYS handling, expiry loops, and persistence restore.

Behavioral parity with reference ``server.go`` (the per-symbol map lives in
SURVEY.md §2.1). The reference's goroutine-per-connection becomes an asyncio
task per connection; the five housekeeping tickers become one asyncio event
loop task (server.go:374-395); everything else is a synchronous call graph
identical in shape to the reference's.
"""

from __future__ import annotations

import asyncio
import json
import logging
import threading
import time
from dataclasses import dataclass, field, replace
from typing import Any, Callable, NamedTuple, Optional

from . import packets as pkts
from .clients import (
    ACK_FIRST_BYTE,
    ACK_REMAINING,
    RUN_FIRST_BYTES,
    Client,
    Clients,
    ConnectionClosedError,
    SessionsDue,
    SliceSocket,
    Will,
)
from .hooks import (
    ON_PACKET_ENCODE,
    ON_PACKET_PROCESSED,
    ON_PACKET_READ,
    ON_PACKET_SENT,
    ON_PUBLISH,
    ON_PUBLISHED,
    ON_QOS_COMPLETE,
    ON_QOS_PUBLISH,
    ON_SELECT_SUBSCRIBERS,
    STORED_CLIENTS,
    STORED_INFLIGHT_MESSAGES,
    STORED_RETAINED_MESSAGES,
    STORED_SUBSCRIPTIONS,
    STORED_SYS_INFO,
    Hook,
    HookOptions,
    Hooks,
)
from .inflight import BEGIN_FULL, BEGIN_PARKED
from .listeners import (
    TYPE_HEALTHCHECK,
    TYPE_MOCK,
    TYPE_SYSINFO,
    TYPE_TCP,
    TYPE_UNIX,
    TYPE_WS,
    Config as ListenerConfig,
    Listener,
    Listeners,
    MockListener,
    TCP,
)
from .packets import (
    CODE_DISCONNECT,
    CODE_DISCONNECT_WILL_MESSAGE,
    CODE_SUCCESS,
    CODE_SUCCESS_IGNORE,
    ERR_BAD_USERNAME_OR_PASSWORD,
    ERR_INLINE_SUBSCRIPTION_HANDLER_INVALID,
    ERR_NOT_AUTHORIZED,
    ERR_PACKET_IDENTIFIER_IN_USE,
    ERR_PACKET_IDENTIFIER_NOT_FOUND,
    ERR_PENDING_CLIENT_WRITES_EXCEEDED,
    ERR_PROTOCOL_VIOLATION_INVALID_SHARED_NO_LOCAL,
    ERR_PROTOCOL_VIOLATION_REQUIRE_FIRST_CONNECT,
    ERR_PROTOCOL_VIOLATION_SECOND_CONNECT,
    ERR_PROTOCOL_VIOLATION_ZERO_NON_ZERO_EXPIRY,
    ERR_QOS_NOT_SUPPORTED,
    ERR_QUOTA_EXCEEDED,
    ERR_RECEIVE_MAXIMUM,
    ERR_REJECT_PACKET,
    ERR_RETAIN_NOT_SUPPORTED,
    ERR_SERVER_BUSY,
    ERR_SERVER_SHUTTING_DOWN,
    ERR_SERVER_UNAVAILABLE,
    ERR_SESSION_TAKEN_OVER,
    ERR_TOPIC_FILTER_INVALID,
    ERR_UNSPECIFIED_ERROR,
    ERR_UNSUPPORTED_PROTOCOL_VERSION,
    QOS_CODES,
    V5_CODES_TO_V3,
    Code,
    FixedHeader,
    Packet,
    PacketStore,
    Properties,
    Subscription,
    UserProperty,
)
from .staging import MatchStage, Parked
from .system import Info
from .utils.mempool import get_buffer, put_buffer
from .utils.loopwitness import DEFAULT_LOOP_PLANE as _LOOP_PLANE
from .utils.proc import rss_bytes
from .topics import (
    NS_CHAR,
    SYS_PREFIX,
    InlineSubFn,
    InlineSubscription,
    TopicsIndex,
    is_shared_filter,
    is_valid_filter,
    ns_local,
    ns_scope_filter,
    ns_scope_topic,
    ns_tenant,
    split_predicate_suffix,
)

VERSION = "0.1.0"  # our framework version (reference tracks 2.7.9)
DEFAULT_SYS_TOPIC_INTERVAL = 1  # seconds between $SYS publishes
LOCAL_LISTENER = "local"
INLINE_CLIENT_ID = "inline"

MAX_INT64 = (1 << 63) - 1
MAX_UINT32 = (1 << 32) - 1


class ListenerIDExistsError(Exception):
    """A listener with the same id already exists."""


class InlineClientNotEnabledError(Exception):
    """Options.inline_client must be True to use inline pub/sub."""


@dataclass
class Compatibilities:
    """Compatibility-mode flags (server.go:86-93)."""

    obscure_not_authorized: bool = False
    passive_client_disconnect: bool = False
    always_return_response_info: bool = False
    restore_sys_info_on_restart: bool = False
    no_inherited_properties_on_ack: bool = False


@dataclass
class Capabilities:
    """Server features and limits (server.go:46-84)."""

    maximum_clients: int = MAX_INT64
    maximum_message_expiry_interval: int = 60 * 60 * 24
    maximum_client_writes_pending: int = 1024 * 8
    maximum_session_expiry_interval: int = MAX_UINT32
    maximum_packet_size: int = 0
    maximum_packet_id: int = 0xFFFF
    receive_maximum: int = 1024
    maximum_inflight: int = 1024 * 8
    topic_alias_maximum: int = 0xFFFF
    shared_sub_available: int = 1
    minimum_protocol_version: int = 3
    compatibilities: Compatibilities = field(default_factory=Compatibilities)
    maximum_qos: int = 2
    retain_available: int = 1
    wildcard_sub_available: int = 1
    sub_id_available: int = 1


@dataclass
class Options:
    """Configurable server options (server.go:96-131)."""

    listeners: list[ListenerConfig] = field(default_factory=list)
    hooks: list[tuple[Hook, Any]] = field(default_factory=list)
    capabilities: Capabilities = field(default_factory=Capabilities)
    client_net_write_buffer_size: int = 0
    client_net_read_buffer_size: int = 0
    logger: Optional[logging.Logger] = None
    sys_topic_resend_interval: int = 0
    inline_client: bool = False
    # route publish-topic matching through the delta-staged device matcher
    # (mqtt_tpu.ops.delta.DeltaMatcher) instead of the host trie walk; results
    # are bit-identical, the index lives on the TPU (SURVEY.md north star)
    device_matcher: bool = False
    # kwargs forwarded to DeltaMatcher (max_levels, out_slots, window,
    # rebuild_after, rebuild_interval, mesh, compact, lazy, ...)
    matcher_opts: Optional[dict] = None
    # publish staging loop (mqtt_tpu.staging): accumulation window and batch
    # cap for device match batches; pipeline depth for in-flight batches
    matcher_stage_window_ms: float = 2.0
    matcher_stage_max_batch: int = 4096
    matcher_stage_max_inflight: int = 4
    # p99 latency budget for one staged publish (staging.MatchStage adapts
    # window + batch cap to hold it); <= 0 disables adaptation
    matcher_stage_latency_budget_ms: float = 250.0
    # overlapped-staging depth (mqtt_tpu.staging): batches in flight
    # across the h2d-tokenize / device-dispatch / d2h-drain legs
    # (ROADMAP item 1); <= 0 falls back to matcher_stage_max_inflight
    matcher_stage_pipeline_depth: int = 3
    # device-resident hit compaction (ops/flat.flat_match_compact):
    # pinned pair-buffer capacity; 0 = adaptive from the observed
    # hits-per-topic EWMA
    matcher_compact_capacity: int = 0
    # read-side decode batching: coalesce frame scans from read loops
    # that wake in the same event-loop tick into one native multi-buffer
    # scan call. Opt-in: it adds one loop-callback hop per socket read,
    # which only pays off at high connection counts. Inside the shard
    # fabric (loop_shards > 1) the gate is PER-SHARD and default-on
    # regardless of this knob.
    scan_coalesce: bool = False
    # event-loop shard fabric (mqtt_tpu.shards / ROADMAP item 4): the
    # connection front-end as N threads each running its own event loop
    # owning thousands of connections, with accepted sockets dispatched
    # to the least-loaded shard. 1 (default) preserves today's
    # single-loop behavior bit-for-bit — no fabric code runs at all.
    loop_shards: int = 1
    # fabric accept mode: "handoff" (default — the main loop accepts
    # and routes each bare socket to the least-loaded shard; exact
    # least-loaded spread) or "reuseport" (every shard binds its own
    # SO_REUSEPORT socket and accepts on its own loop; kernel load
    # balancing, no hand-off hop; falls back to handoff where
    # SO_REUSEPORT is unavailable)
    loop_shard_accept: str = "handoff"
    # degradation manager (mqtt_tpu.resilience): wrap every device dispatch
    # in a circuit breaker + hang watchdog; timeouts/errors/corrupt results
    # route matching to the bit-identical host trie and background probes
    # re-admit the device once verified healthy. Default on — a flapping
    # link must degrade, never wedge.
    matcher_resilience: bool = True
    # consecutive failures before the breaker trips OPEN
    breaker_failure_threshold: int = 3
    # per-batch hang budget: a dispatch not resolved within this is
    # abandoned and served from the host walk. A last-resort hang bound,
    # NOT a latency control. Cold-compile time does not count against it.
    breaker_watchdog_ms: float = 5000.0
    # half-open probe schedule: exponential backoff from the base delay up
    # to the max, +/- the jitter fraction; this many verified-healthy
    # probes close the breaker
    breaker_probe_backoff_ms: float = 500.0
    breaker_probe_backoff_max_ms: float = 30000.0
    breaker_probe_jitter: float = 0.1
    breaker_probe_successes: int = 2
    # topics differentially re-walked on the host per healthy batch (the
    # corrupt-result tripwire); 0 disables sampling outside probes
    breaker_verify_sample: int = 1
    # raise the process-global CPython GC thresholds for broker throughput
    # (utils/gctune.py). Default on for the standalone broker; an embedding
    # application that wants its own GC cadence sets this False (the change
    # is process-wide and logged at info level)
    gc_tuning: bool = True
    # broker-wide overload control plane (mqtt_tpu.overload): a NORMAL ->
    # THROTTLE -> SHED governor over staging depth, aggregate outbound
    # backlog, cluster peer buffers, and an optional RSS watermark.
    # Default on — a publish storm must degrade predictably (throttled
    # reads, 0x97 sheds, slow-consumer eviction), never OOM.
    overload_control: bool = True
    # hysteresis bands over the max normalized pressure (enter > exit)
    overload_throttle_enter: float = 0.70
    overload_throttle_exit: float = 0.50
    overload_shed_enter: float = 0.90
    overload_shed_exit: float = 0.65
    # minimum ms in a state before de-escalating (escalation is instant)
    overload_min_dwell_ms: float = 500.0
    # governor evaluation cadence (lazy re-sample on the data plane)
    overload_eval_interval_ms: float = 250.0
    # per-client quota window (publish/shed budgets); 0 = eval interval
    overload_quota_window_ms: float = 0.0
    # THROTTLE: per-client publishes per window before reads pause, and
    # the pause applied to each subsequent read
    overload_publish_quota: int = 2048
    overload_throttle_delay_ms: float = 50.0
    # SHED: per-client publishes admitted per window (excess sheds:
    # QoS0 dropped, QoS1/2 acked 0x97 Quota Exceeded)
    overload_shed_quota: int = 256
    # SHED: outbound-queue-full grace before slow-consumer eviction
    # (DISCONNECT 0x97)
    overload_eviction_grace_ms: float = 2000.0
    # staging admission bound: MatchStage._pending never exceeds this
    # (overflow resolves via the deadline-aware host walk)
    overload_stage_max_pending: int = 8192
    # per-client transport write-buffer watermark (bytes): a client whose
    # buffered-but-unsent outbound bytes stay above this past the grace
    # window is a slow consumer (asyncio buffers writes unboundedly — the
    # broker-side OOM vector a non-reading subscriber creates)
    overload_client_buffer_limit_bytes: int = 1024 * 1024
    # aggregate outbound backlog (sum of queued publishes across all
    # clients) that normalizes to pressure 1.0
    overload_max_outbound_backlog: int = 65536
    # RSS watermark in MB that normalizes to pressure 1.0; 0 disables
    # the memory signal
    overload_memory_limit_mb: float = 0.0
    # mesh federation (mqtt_tpu.cluster gossip -> mqtt_tpu.overload):
    # fold peer workers' advertised governor postures into this worker's
    # pressure as a decayed-max "peers" signal, so one shedding worker
    # raises the whole mesh to THROTTLE instead of the rest pumping
    # publishes into it
    overload_federation: bool = True
    # scale applied to the peers signal (< 1 so a SHED advert lands the
    # mesh in THROTTLE, not a sympathetic full-mesh SHED cascade)
    overload_federation_weight: float = 0.9
    # gossip adverts decay linearly to zero over this TTL and then age
    # out entirely (a dead worker must not pin the mesh's posture)
    overload_federation_ttl_ms: float = 15000.0
    # per-listener CONNECT admission: while THROTTLE/SHED, new CONNECTs
    # on admission-gated listeners are refused with CONNACK 0x97 (0x89
    # while the server drains); False disables the gate entirely
    overload_admission: bool = True
    # always-admit reserve per quota window for $SYS/admin-ACL clients
    # (the operator's monitoring session must get in mid-storm)
    overload_admission_reserve: int = 2
    # priority-weighted shedding: class name -> quota multiplier applied
    # to both the shed and publish quotas (None = every client weighs 1)
    overload_priority_classes: Optional[dict] = None
    # username-or-client-id -> class name (assigned at CONNECT; embedders
    # can also set cl.priority_weight directly from an on_connect hook)
    overload_priority_users: Optional[dict] = None
    # mesh peer health (mqtt_tpu.cluster): consecutive unanswered pings
    # before a peer goes SUSPECT (QoS>0 forwards park in a bounded
    # buffer) and before it is declared PARTITIONED (park flushed into
    # the partition drop counters, link aborted for a clean re-dial)
    cluster_peer_health_suspect_pings: int = 2
    cluster_peer_health_partition_pings: int = 5
    # seconds-dialable SUSPECT window (ISSUE 8 satellite): when > 0 this
    # replaces the missed-pong COUNT with a wall-clock grace — the peer
    # goes SUSPECT after ~this many seconds without a pong (rounded up
    # to whole ping intervals). 0 keeps the legacy pings knob.
    cluster_suspect_window_s: float = 0.0
    # byte budget of each SUSPECT peer's park buffer (oldest spill first)
    cluster_peer_park_max_bytes: int = 1 << 20
    # mesh topology (ISSUE 9): "mesh" keeps the PR 5 all-pairs fabric
    # (every worker dials every peer — fine to ~8 workers); "tree" routes
    # over the epoch-stamped spanning tree mqtt_tpu.mesh_topology elects,
    # keeping per-worker links and gossip O(degree) at 32+ workers.
    # Mesh-wide: every worker must run the same mode.
    cluster_topology: str = "mesh"
    # spanning-tree branching factor (per-worker links <= degree + 1)
    cluster_tree_degree: int = 4
    # interest-summary bloom size in bits (per edge; must be a multiple
    # of 8 — bigger = fewer false-positive forwards at more gossip bytes)
    cluster_summary_bits: int = 4096
    # (origin, boot) duplicate-suppression window in sequence numbers
    cluster_dup_window: int = 8192
    # cross-machine mesh transport (ISSUE 17): "unix" keeps the on-box
    # socket-dir fabric; "tcp" listens on cluster_base_port + worker_id
    # (per-worker pins via cluster_peer_addrs: {worker: "host:port"}).
    # Mesh-wide: every worker must run the same transport.
    cluster_transport: str = "unix"
    cluster_host: str = "127.0.0.1"
    cluster_base_port: int = 0
    cluster_peer_addrs: Optional[dict] = None
    # mutual-TLS on TCP peer links: cert/key identify this worker, and a
    # configured CA makes BOTH directions verify (the accepting side
    # demands a client cert too). Empty cert = plaintext TCP.
    cluster_tls_cert: str = ""
    cluster_tls_key: str = ""
    cluster_tls_ca: str = ""
    # WAN dial/keepalive tuning: a blackholed SYN fails onto the backoff
    # ladder after this many seconds; keepalive > 0 arms kernel TCP
    # keepalive probes at that idle interval on every peer link
    cluster_connect_timeout_s: float = 5.0
    cluster_keepalive_s: float = 0.0
    # predicate push-down (ISSUE 17): max interned predicate digests
    # carried per edge summary — past the cap the digest plane degrades
    # to conservative pass-through (0 disables push-down entirely)
    cluster_summary_digests: int = 64
    # MQTT+ payload-predicate subscriptions (mqtt_tpu.predicates): parse
    # `$GT{...}`-style suffixes off SUBSCRIBE filters, filter fan-out by
    # payload, evaluate the compiled rule table on device inside the
    # staged match batch (host interpreter = oracle + degradation path).
    # Default on — an unpredicated broker pays one attribute read per
    # publish and stays bit-identical.
    predicate_filters: bool = True
    # device rule-table cap: rules registered past it are evaluated by
    # the host interpreter only (degraded, never refused)
    predicate_max_rules: int = 1 << 20
    # differential oracle cadence: 1-in-N predicated publishes re-derive
    # every device verdict from the raw payload on the host and count
    # mismatches (0 disables sampling)
    predicate_oracle_sample: int = 64
    # secure multi-tenant plane (mqtt_tpu.tenancy): clients resolve to a
    # tenant at CONNECT (username first, then client id — the
    # overload_priority_users idiom) and from then on every broker key
    # they touch — trie filters, retained topics, $SHARE groups, the
    # client-registry id, cluster interest summaries — carries the
    # tenant's namespace prefix, so cross-tenant delivery is impossible
    # by construction. Off by default: with it off, no tenancy code runs.
    tenancy: bool = False
    # tenant registry: name -> {quota_class: str, encrypted: [topic
    # prefix, ...], keys: {client-id-or-username: 32-hex-char AES-128
    # key, ...}}. quota_class rides the governor's priority-class
    # machinery (overload_priority_classes supplies the weights).
    tenants: Optional[dict] = None
    # username-or-client-id -> tenant name (resolved at CONNECT)
    tenant_users: Optional[dict] = None
    # tenant for unmapped clients; "" keeps them in the global namespace
    tenant_default: str = ""
    # per-tenant durable COUNT caps (ISSUE 16 / MQT-TZ quota residual):
    # the default maximum number of retained topics / stored
    # subscriptions a tenant may hold; a tenant dict may override with
    # its own `max_retained` / `max_subscriptions`. 0 = unlimited.
    # Enforced structurally in the namespaced stores (refused with v5
    # 0x97 Quota exceeded and counted per tenant) so a runaway tenant
    # cannot grow durable memory past its cap. Global (untenanted)
    # clients are uncapped.
    tenant_max_retained: int = 0
    tenant_max_subscriptions: int = 0
    # device-resident retained matching (mqtt_tpu.ops.retained): serve
    # wildcard-SUBSCRIBE retained fan-out from the flat CSR kernel run
    # in reverse, with the host retained walk as 1-in-N differential
    # oracle behind a CircuitBreaker (host wins mismatches; an open
    # breaker degrades all retained matching to the host walk). Off by
    # default: the host walk is exact and retained fan-out is off the
    # publish hot path.
    retained_matcher: bool = False
    # 1-in-N oracle cadence for the retained kernel (0 disables the
    # sampled oracle; breaker probes still verify fully)
    retained_oracle_sample: int = 16
    # restart re-registration batch size: persisted subscriptions and
    # retained messages re-enter the trie through the bulk-insert path
    # in chunks of this many (staging.bulk_register / bulk_retain)
    durable_restore_batch: int = 4096
    # MQT-TZ re-encryption stage (mqtt_tpu.tenancy.RecryptEngine +
    # ops/recrypt): publishes in a tenant's `encrypted` namespaces are
    # decrypted once with the publisher's key and re-encrypted per
    # subscriber as ONE batched AES-CTR keystream dispatch per fan-out
    # tick (vectorized-host oracle + breaker degradation, the
    # matcher/predicate posture). Requires tenancy.
    recrypt: bool = True
    # differential-oracle cadence: 1-in-N device keystream dispatches
    # are re-derived on the host and compared bit-for-bit (0 disables)
    recrypt_oracle_sample: int = 64
    # dispatches below this many 16-byte keystream blocks run on the
    # host outright (a tiny batch's device round trip only adds latency)
    recrypt_device_min_blocks: int = 4
    # unified telemetry plane (mqtt_tpu.telemetry): per-publish stage
    # clock sampled 1-in-N, histogram metrics, Prometheus exposition at
    # GET /metrics (sysinfo listener), the retained
    # $SYS/broker/telemetry/# tree, and a flight recorder that dumps a
    # JSON trace when the governor enters SHED or the breaker trips.
    # Default on — sampling keeps the unsampled hot path at one integer
    # increment per publish.
    telemetry: bool = True
    # stage-clock sampling: 1-in-N publishes carry a clock (0 disables
    # stage sampling; batch/queue histograms still populate)
    telemetry_sample: int = 64
    # flight-recorder ring size (recent sampled stage records)
    telemetry_ring: int = 256
    # flight-recorder dump directory; "" = <tempdir>/mqtt_tpu_flight
    telemetry_dump_dir: str = ""
    # minimum ms between flight-recorder dumps (a flapping posture must
    # not fill the disk)
    telemetry_dump_min_interval_ms: float = 30000.0
    # trace plane (mqtt_tpu.tracing): 1-in-N publishes carry a full
    # trace context — a span tree through decode -> admission ->
    # staging_wait -> h2d -> device_dispatch -> d2h -> fanout plus
    # per-peer forward spans, joined across the worker mesh by the
    # trace id riding cluster frames. Exported as Chrome trace-event
    # JSON at GET /traces and in trigger dumps. Default on (requires
    # telemetry); the unsampled hot path pays one extra modulo.
    trace: bool = True
    # 1-in-N publishes carry a trace (0 disables tracing outright)
    trace_sample: int = 64
    # span-ring size (finished spans retained for /traces and dumps)
    trace_ring: int = 4096
    # per-bucket (value, trace_id) exemplars on the stage histograms,
    # rendered OpenMetrics-style on /metrics — links a p99 bucket to a
    # concrete recorded trace. NOTE: plain Prometheus text-format
    # scrapers that reject exemplar suffixes need this off.
    trace_exemplars: bool = True
    # stamp traced publishes with a v5 `trace-id` user property so
    # subscribers see the trace id (default OFF: it mutates the wire
    # bytes of sampled publishes). Inbound v5 publishes carrying the
    # property ADOPT the client's trace id regardless, rate-bounded by
    # trace_adopt_max_per_s.
    trace_user_property: bool = False
    # client-driven adoptions admitted per second (a client stamping
    # every publish must not bypass trace_sample or flood the span
    # ring); 0 disables adoption entirely
    trace_adopt_max_per_s: int = 64
    # when set, serve() starts a jax.profiler trace into this directory
    # and close() stops it — the deep-dive companion to the host-side
    # duty-cycle numbers ("" disables; requires a device matcher)
    trace_jax_profiler_dir: str = ""
    # lock-contention plane (mqtt_tpu.utils.locked): arm the named-lock
    # wait/hold instrumentation and export it on /metrics. Opt-out knob
    # — a disarmed lock costs one bool test over the bare acquire.
    profile_locks: bool = True
    # cluster-wide SLO observatory (ISSUE 14, mqtt_tpu.slo): the
    # per-tenant delivery-latency SLI (publish arrival at decode ->
    # frame flushed, riding the sampled stage clocks — the unsampled
    # hot path pays nothing, the sampled path one dict probe) and the
    # multi-window burn-rate engine over declared objectives. Default
    # on; False disables SLI stamping AND the engine.
    slo: bool = True
    # declarative objectives, e.g. ["p99 delivery < 50ms over 5m",
    # "shed ratio < 0.1%"] — grammar in mqtt_tpu.slo; unparseable lines
    # are logged and skipped, never fatal. None/empty = SLIs recorded,
    # no engine.
    slo_objectives: Optional[list] = None
    # burn-rate level both windows must exceed to breach (1.0 = the
    # budget is being spent exactly as fast as allowed)
    slo_burn_threshold: float = 1.0
    # per-device observability plane (ISSUE 18, mqtt_tpu.ops.
    # devicestats): per-chip HBM gauges, the compile-event ledger, the
    # shard-skew gauge, GET /devices, $SYS/broker/devices/#, and the
    # devices_*.json trigger-dump sibling. Default on (requires
    # telemetry + a device matcher to say anything interesting, but the
    # plane itself is host-side and backend-agnostic).
    device_stats: bool = True
    # live/limit HBM occupancy at or above which /healthz reports the
    # device plane degraded (never flips readiness); the "hbm ratio"
    # SLO objective is the alerting twin of this knob
    device_hbm_watermark: float = 0.9
    # mesh metric federation (mqtt_tpu.cluster _T_METRICS): per-worker
    # registry summaries ride the mesh at gossip cadence with
    # per-subtree fold; the tree root serves GET /metrics/cluster and
    # /cluster/slo for the whole mesh. False disables send AND store.
    cluster_metrics: bool = True
    # federated summaries older than this age out of scrapes (a dead
    # worker must not pin stale totals)
    cluster_metrics_max_age_s: float = 120.0

    def ensure_defaults(self) -> None:
        """Sane defaults when unset (server.go:208-235)."""
        self.capabilities.maximum_packet_id = 0xFFFF  # spec maximum
        if self.capabilities.maximum_inflight == 0:
            self.capabilities.maximum_inflight = 1024 * 8
        if self.sys_topic_resend_interval == 0:
            self.sys_topic_resend_interval = DEFAULT_SYS_TOPIC_INTERVAL
        if self.client_net_write_buffer_size == 0:
            self.client_net_write_buffer_size = 1024 * 2
        if self.client_net_read_buffer_size == 0:
            self.client_net_read_buffer_size = 1024 * 2
        # staging knobs are config-reachable: a zero/negative max_batch
        # would busy-spin the collector on empty batches, and a zero
        # max_inflight turns the bounded queue unbounded (asyncio.Queue
        # semantics) — normalize both like the buffer sizes above
        if self.matcher_stage_max_batch <= 0:
            self.matcher_stage_max_batch = 4096
        if self.matcher_stage_max_inflight <= 0:
            self.matcher_stage_max_inflight = 4
        if self.matcher_stage_window_ms < 0:
            self.matcher_stage_window_ms = 0.0
        # breaker knobs are config-reachable too: zero/negative values
        # would trip instantly or busy-probe — normalize to the defaults
        if self.breaker_failure_threshold <= 0:
            self.breaker_failure_threshold = 3
        if self.breaker_watchdog_ms <= 0:
            self.breaker_watchdog_ms = 5000.0
        if self.breaker_probe_backoff_ms <= 0:
            self.breaker_probe_backoff_ms = 500.0
        if self.breaker_probe_backoff_max_ms < self.breaker_probe_backoff_ms:
            self.breaker_probe_backoff_max_ms = max(
                self.breaker_probe_backoff_ms, 30000.0
            )
        # overload knobs are config-reachable: inverted hysteresis bands
        # would flap on every evaluation and zero caps would divide the
        # pressure signals — normalize like the knobs above
        if self.overload_throttle_exit > self.overload_throttle_enter:
            self.overload_throttle_exit = self.overload_throttle_enter
        if self.overload_shed_exit > self.overload_shed_enter:
            self.overload_shed_exit = self.overload_shed_enter
        if self.overload_shed_enter < self.overload_throttle_enter:
            self.overload_shed_enter = self.overload_throttle_enter
        if self.overload_stage_max_pending <= 0:
            self.overload_stage_max_pending = 8192
        if self.overload_client_buffer_limit_bytes <= 0:
            self.overload_client_buffer_limit_bytes = 1024 * 1024
        if self.overload_max_outbound_backlog <= 0:
            self.overload_max_outbound_backlog = 65536
        if self.overload_eval_interval_ms <= 0:
            self.overload_eval_interval_ms = 250.0
        if self.overload_quota_window_ms < 0:
            self.overload_quota_window_ms = 0.0
        if self.overload_min_dwell_ms < 0:
            self.overload_min_dwell_ms = 500.0
        if self.overload_throttle_delay_ms < 0:
            self.overload_throttle_delay_ms = 50.0
        if self.overload_eviction_grace_ms < 0:
            # a negative grace would evict on the FIRST sweep after any
            # transient backlog — mass-disconnecting healthy-but-busy
            # consumers the moment the broker sheds
            self.overload_eviction_grace_ms = 2000.0
        if self.overload_publish_quota <= 0:
            self.overload_publish_quota = 2048
        if self.overload_shed_quota <= 0:
            self.overload_shed_quota = 256
        # federation/admission/health knobs are config-reachable too
        if self.overload_priority_classes:
            # sanitize ONCE at startup: _assign_priority_class runs on
            # the CONNECT path, where a non-numeric weight from a config
            # typo would otherwise raise mid-handshake and take out the
            # whole class's connects (no CONNACK at all)
            clean = {}
            for klass, weight in self.overload_priority_classes.items():
                try:
                    clean[klass] = float(weight)
                except (TypeError, ValueError):
                    logging.getLogger("mqtt_tpu").warning(
                        "overload_priority_classes[%r]=%r is not a number; "
                        "class falls back to weight 1.0",
                        klass,
                        weight,
                    )
            self.overload_priority_classes = clean
        if self.overload_federation_weight <= 0:
            self.overload_federation_weight = 0.9
        if self.overload_federation_ttl_ms <= 0:
            self.overload_federation_ttl_ms = 15000.0
        if self.overload_admission_reserve < 0:
            self.overload_admission_reserve = 0
        if self.cluster_peer_health_suspect_pings <= 0:
            self.cluster_peer_health_suspect_pings = 2
        if self.cluster_peer_health_partition_pings <= self.cluster_peer_health_suspect_pings:
            # PARTITIONED must come strictly after SUSPECT, or the park
            # buffer never gets a heal window at all
            self.cluster_peer_health_partition_pings = (
                self.cluster_peer_health_suspect_pings + 3
            )
        if self.cluster_peer_park_max_bytes <= 0:
            self.cluster_peer_park_max_bytes = 1 << 20
        if self.cluster_suspect_window_s < 0:
            self.cluster_suspect_window_s = 0.0  # 0 = legacy pings knob
        # topology knobs are config-reachable: an unknown mode falls back
        # to the all-pairs mesh (never a refused boot), the tree degree
        # needs >= 1 child slot, and the summary bloom must be whole
        # bytes with enough slots to be worth probing
        if str(self.cluster_topology).lower() not in ("mesh", "tree"):
            self.cluster_topology = "mesh"
        else:
            self.cluster_topology = str(self.cluster_topology).lower()
        if self.cluster_tree_degree < 1:
            self.cluster_tree_degree = 4
        if self.cluster_summary_bits < 64 or self.cluster_summary_bits % 8:
            self.cluster_summary_bits = 4096
        if self.cluster_dup_window < 1:
            self.cluster_dup_window = 8192
        # transport knobs are config-reachable: an unknown transport
        # falls back to the on-box unix fabric (never a refused boot),
        # ports clamp into range, and the WAN timers stay sane
        if str(self.cluster_transport).lower() not in ("unix", "tcp"):
            self.cluster_transport = "unix"
        else:
            self.cluster_transport = str(self.cluster_transport).lower()
        if not 0 <= self.cluster_base_port <= 65535:
            self.cluster_base_port = 0
        if self.cluster_connect_timeout_s <= 0:
            self.cluster_connect_timeout_s = 5.0
        if self.cluster_keepalive_s < 0:
            self.cluster_keepalive_s = 0.0
        if self.cluster_summary_digests < 0:
            self.cluster_summary_digests = 64
        # predicate knobs are config-reachable: a zero/negative rule cap
        # would refuse every predicate, a negative sample means "default"
        if self.predicate_max_rules <= 0:
            self.predicate_max_rules = 1 << 20
        if self.predicate_oracle_sample < 0:
            self.predicate_oracle_sample = 64
        # tenancy knobs are config-reachable: a negative oracle sample
        # means "default", the block floor needs >= 1
        if self.recrypt_oracle_sample < 0:
            self.recrypt_oracle_sample = 64
        if self.recrypt_device_min_blocks < 1:
            self.recrypt_device_min_blocks = 4
        # durable-plane knobs are config-reachable: negative caps mean
        # "unlimited", a negative oracle sample means "default", and the
        # restore batch needs >= 1 or bulk chunking never drains
        if self.tenant_max_retained < 0:
            self.tenant_max_retained = 0
        if self.tenant_max_subscriptions < 0:
            self.tenant_max_subscriptions = 0
        if self.retained_oracle_sample < 0:
            self.retained_oracle_sample = 16
        if self.durable_restore_batch < 1:
            self.durable_restore_batch = 4096
        # telemetry knobs are config-reachable: a negative sample rate
        # means "default", a zero one disables stage sampling outright
        if self.telemetry_sample < 0:
            self.telemetry_sample = 64
        if self.telemetry_ring <= 0:
            self.telemetry_ring = 256
        if self.telemetry_dump_min_interval_ms < 0:
            self.telemetry_dump_min_interval_ms = 30000.0
        # trace knobs are config-reachable: a negative sample rate means
        # "default", zero disables tracing; the ring must hold something
        if self.trace_sample < 0:
            self.trace_sample = 64
        if self.trace_ring <= 0:
            self.trace_ring = 4096
        if self.trace_adopt_max_per_s < 0:
            self.trace_adopt_max_per_s = 64
        # fabric knobs are config-reachable: a negative shard count
        # means single-loop, an unknown accept mode falls back to the
        # hand-off router (never a refused boot)
        if self.loop_shards < 1:
            self.loop_shards = 1
        if str(self.loop_shard_accept).lower() not in ("handoff", "reuseport"):
            self.loop_shard_accept = "handoff"
        else:
            self.loop_shard_accept = str(self.loop_shard_accept).lower()
        if self.logger is None:
            self.logger = logging.getLogger("mqtt_tpu")


_VIEW_CLS: Any = None
_VIEW_CLS_RESOLVED = False


def _view_class():
    """The C ``SubscribersView`` type (native/accelmod.c) or None —
    resolved once. Without the C module no view can ever reach
    ``_fan_out``, so None simply disables the lazy branch."""
    global _VIEW_CLS, _VIEW_CLS_RESOLVED
    if not _VIEW_CLS_RESOLVED:
        from .native import accel

        mod = accel()
        _VIEW_CLS = getattr(mod, "SubscribersView", None) if mod else None
        _VIEW_CLS_RESOLVED = True
    return _VIEW_CLS


def publish_frame_body_offset(frame: bytes) -> int:
    """Offset of a raw PUBLISH frame's variable header (skips the fixed
    header's remaining-length varint). The caller guarantees a frame the
    scanner accepted, so the varint terminates within 4 bytes."""
    off = 1
    while frame[off] & 0x80:
        off += 1
    return off + 1


def publish_frame_topic(frame: bytes):
    """``(topic, body_offset)`` parsed from a raw PUBLISH frame, or None
    when the frame is truncated or the topic is not valid UTF-8. The one
    shared parse for every fast-path delivery leg — try_fast_publish's
    inline gates, fast_deliver_frame, and the cluster's forwarded-frame
    delivery (mqtt_tpu.cluster) — so framing rules change in one place."""
    body_offset = publish_frame_body_offset(frame)
    n = len(frame)
    if body_offset + 2 > n:
        return None
    tl = (frame[body_offset] << 8) | frame[body_offset + 1]
    t0 = body_offset + 2
    if n < t0 + tl:
        return None
    try:
        return frame[t0 : t0 + tl].decode("utf-8"), body_offset
    except UnicodeDecodeError:
        return None


# a hook that provides one of these takes the packet a QoS0 frame
# passthrough never builds (Server.fast_publish_eligible)
_FAST_PUBLISH_EVENTS = (
    ON_PACKET_READ,
    ON_PUBLISH,
    ON_PACKET_ENCODE,
    ON_PACKET_SENT,
    ON_PUBLISHED,
    ON_PACKET_PROCESSED,
)
# a hook that provides one of these sees, per frame, what the ingest run
# does once a run or not at all: the packet as it was read, the publish
# before it is taken, the inflight bookkeeping around a QoS1 ack, the
# ack as a packet (Server.ingest_run)
_INGEST_RUN_EVENTS = (
    ON_PACKET_READ,
    ON_PUBLISH,
    ON_QOS_PUBLISH,
    ON_QOS_COMPLETE,
    ON_PACKET_ENCODE,
    ON_PACKET_SENT,
)
# a hook that provides one of these is shown every PUBACK: the packet
# as it was read, the completion of its delivery (a storage hook
# persists it), the packet once processed (Server.ack_run)
_ACK_RUN_EVENTS = (ON_PACKET_READ, ON_QOS_COMPLETE, ON_PACKET_PROCESSED)
# a hook that provides this is shown each new outbound inflight entry
_QOS_PUBLISH_EVENTS = (ON_QOS_PUBLISH,)


class _Ops:
    """Server values propagated to clients (server.go:159-164).
    ``fast_publish`` is the server's QoS0 frame-passthrough entry point
    (None until the server wires it)."""

    def __init__(self, options: Options, info: Info, hooks: Hooks, log: logging.Logger) -> None:
        self.options = options
        self.info = info
        self.hooks = hooks
        self.log = log
        self.fast_publish: Optional[Callable[..., bool]] = None
        self.fast_publish_eligible: Optional[Callable[..., bool]] = None
        # the overload governor (mqtt_tpu.overload); None = ungoverned.
        # Clients consult it for the THROTTLE read-delay verdict.
        self.overload: Optional[Any] = None
        # the telemetry plane (mqtt_tpu.telemetry); None = uninstrumented.
        # Clients consult it for the publish stage clock and the sampled
        # outbound queue-wait stamps.
        self.telemetry: Optional[Any] = None
        # read-side scan coalescer (clients.ScanGate); None = per-socket
        # scans. Set by the server when Options.scan_coalesce is on.
        self.scan_gate: Optional[Any] = None
        # the device pipeline profiler (mqtt_tpu.tracing.DeviceProfiler);
        # None = no device matcher or tracing off. Read loops feed it
        # their per-publish ingest time while a profiler session is live.
        self.profiler: Optional[Any] = None
        # calls that reached a client's socket: a transport write (one a
        # packet, or one a cork: a socket read's packets, a completion
        # slice's deliveries) and each send of the native fan-out flush.
        # A plain add on the writing loop.
        self.socket_sends = 0
        # wake-ups of a connection's frame scan on data, by either
        # feeder of Client.read: the stream feeder's ``_read_more`` that
        # returned bytes (one recv, or two the stream joined; one for a
        # large body read to its end), the direct feeder's
        # ``buffer_updated`` that ran the scan (one recv; the chunks of a
        # known partial packet count once, and the bytes buffered while
        # the gate held count once, when its turn takes them up). Of
        # them ``direct_reads`` are the direct feeder's. Plain adds on
        # the reading loop.
        self.socket_reads = 0
        self.direct_reads = 0
        # the server's entry point for a scan's run of PUBLISH frames
        # (Server.ingest_run; None until the server wires it), the runs
        # that took at least one publish and the publishes they took in.
        # Plain adds on the reading loop.
        self.ingest_run: Optional[Callable[..., int]] = None
        self.ingest_runs = 0
        self.ingest_run_publishes = 0
        # the same for a scan's stretch of bare PUBACK frames
        # (Server.ack_run): the runs, and the frames they took in
        self.ack_run: Optional[Callable[..., int]] = None
        self.ack_runs = 0
        self.ack_run_acks = 0
        # the most target ids one completion slice has looked up at once
        # (Server._complete_staged: its ``ids`` list): one compare a slice
        self.slice_targets_max = 0
        # the three ways a delivery of the encode-once fan-out leaves
        # (Server._flush_variant): the native flush (a ready socket), an
        # open cork (its read's, or the completion slice's), the bounded
        # outbound queue (a socket with a backlog: one write a frame, by
        # its write loop); and the deliveries that queue refused, full
        # (each is also one of ``info.messages_dropped``). Plain adds on
        # the writing loop, once a variant.
        self.deliveries_flush = 0
        self.deliveries_cork = 0
        self.deliveries_queue = 0
        self.deliveries_dropped_full = 0
        # a socket's cork (clients.Client._write / _uncork): corks
        # written (one transport write each), the packets they held, and
        # of the writes those a cork cut at CORK_MAX_BYTES forced early
        self.cork_writes = 0
        self.cork_frames = 0
        self.cork_early_writes = 0
        # times the encode-once fan-out read a socket's readiness
        # (closed, TLS, the transport's buffer, the outbound queue):
        # once a delivery in Server._flush_variant, once a slice for a
        # socket the slice keeps a record of (clients.SliceSocket)
        self.socket_checks = 0
        # shared-group selection (Server._select_shared): the groups
        # selected, the picked members with no connected client, and,
        # while a profiler session is live, the leg's time: the forced
        # materialization of a lazy result that holds groups, the hooks'
        # pick, select_shared and merge_shared_selected. Of the groups,
        # those a completion slice picked in its prologue, before it
        # read its targets (Server._complete_staged). Plain adds on the
        # fan-out's loop.
        self.share_groups = 0
        self.share_picks_offline = 0
        self.share_select_ns = 0
        self.share_groups_early = 0
        # the encode-once fan-out's QoS>0 deliveries (Server._flush_variant):
        # those that reached the inflight bookkeeping, those of them that
        # dispatched on_qos_publish (a hook provides it), and, while a
        # profiler session is live, the bookkeeping's time (the entry's
        # clone, Inflight.begin, the hooks). Plain adds, once a variant.
        self.qos_begins = 0
        self.qos_begins_hooked = 0
        self.qos_begin_ns = 0
        # the server's settlement of a slice's run of one socket's QoS>0
        # deliveries (Server._settle_run; None until the server wires
        # it), the runs it settled and the entries they held
        self.qos_run: Optional[Callable[..., None]] = None
        self.qos_runs = 0
        self.qos_run_entries = 0
        # the 1 Hz housekeeping tick (Server.housekeep): ticks, sessions
        # its expiry and eviction passes looked at (SessionsDue's sets;
        # a loop shard's eviction sweep adds its own), and while a
        # profiler session is live the passes' time; beside them the
        # $SYS publish's time, armed only too. Plain adds on the loop.
        self.housekeep_ticks = 0
        self.housekeep_visits = 0
        self.housekeep_ns = 0
        self.sys_publish_ns = 0


class _Counter(NamedTuple):
    """How one ``_Ops`` count leaves the broker: as ``attr`` in the
    profiler's slice snapshots (``Server._slice_counters``), as
    ``metric`` with ``help`` on ``/metrics`` (a ``kind``: counter or
    gauge; divided by ``scale``, the attribute's units a metric unit),
    and as a retained ``$SYS`` topic at ``sys_path`` (None: none)."""

    attr: str
    metric: str
    help: str
    sys_path: Optional[str]
    kind: str = "counter"
    scale: int = 1


# every ``_Ops`` count the broker exports, once: a new count is one
# attribute on ``_Ops`` and one row here
_OPS_COUNTERS = (
    _Counter(
        "socket_sends", "mqtt_tpu_socket_sends_total",
        "Calls that reached a client's socket: transport writes "
        "(one a packet, or one a cork) and the native fan-out "
        "flush's sends",
        None,
    ),
    _Counter(
        "socket_reads", "mqtt_tpu_socket_reads_total",
        "Wake-ups of a connection's read loop on data: one recv "
        "each (the stream may join two)",
        None,
    ),
    _Counter(
        "direct_reads", "mqtt_tpu_direct_reads_total",
        "Of the socket reads, those taken in inside the "
        "transport's read callback by the broker's own protocol "
        "(the rest came through a stream reader)",
        "/broker/ingest/direct_reads",
    ),
    _Counter(
        "ingest_runs", "mqtt_tpu_ingest_runs_total",
        "Runs of PUBLISH frames a read loop handed to the ingest "
        "run in one call that took at least one publish",
        "/broker/ingest/runs",
    ),
    _Counter(
        "ingest_run_publishes", "mqtt_tpu_ingest_run_publishes_total",
        "Publishes taken in by ingest runs (the rest took the "
        "per-frame path: mqtt_tpu_messages_received_total has both)",
        "/broker/ingest/run_publishes",
    ),
    _Counter(
        "ack_runs", "mqtt_tpu_ack_runs_total",
        "Stretches of bare PUBACK frames a read loop handed to the "
        "ack run in one call that took them",
        "/broker/ingest/ack_runs",
    ),
    _Counter(
        "ack_run_acks", "mqtt_tpu_ack_run_acks_total",
        "PUBACK frames taken in by ack runs (the rest took the "
        "per-frame path)",
        "/broker/ingest/ack_run_acks",
    ),
    _Counter(
        "slice_targets_max", "mqtt_tpu_stage_slice_targets_max",
        "Most target ids one completion slice of a staged batch has "
        "looked up at once (high-water mark since start)",
        "/broker/overload/stage_slice_targets_max",
        kind="gauge",
    ),
    _Counter(
        "deliveries_flush", "mqtt_tpu_deliveries_flush_total",
        "Deliveries of the encode-once fan-out that left by the "
        "native flush (a ready socket: idle transport, empty queue)",
        "/broker/egress/deliveries_flush",
    ),
    _Counter(
        "deliveries_cork", "mqtt_tpu_deliveries_cork_total",
        "Deliveries of the encode-once fan-out that joined a "
        "socket's open cork (its read's, or the completion slice's)",
        "/broker/egress/deliveries_cork",
    ),
    _Counter(
        "deliveries_queue", "mqtt_tpu_deliveries_queue_total",
        "Deliveries of the encode-once fan-out that took a "
        "socket's bounded outbound queue (one write a frame)",
        "/broker/egress/deliveries_queue",
    ),
    _Counter(
        "deliveries_dropped_full", "mqtt_tpu_deliveries_dropped_full_total",
        "Deliveries of the encode-once fan-out the outbound queue "
        "refused, full (also in mqtt_tpu_messages_dropped_total)",
        "/broker/egress/deliveries_dropped_full",
    ),
    _Counter(
        "cork_writes", "mqtt_tpu_cork_writes_total",
        "Corks written: one transport write for the packets a "
        "socket's read or a completion slice held back",
        "/broker/egress/cork_writes",
    ),
    _Counter(
        "cork_frames", "mqtt_tpu_cork_frames_total",
        "Packets that joined a socket's cork",
        "/broker/egress/cork_frames",
    ),
    _Counter(
        "cork_early_writes", "mqtt_tpu_cork_early_writes_total",
        "Of the corks written, those a cork past its byte bound "
        "forced before its opener closed it",
        "/broker/egress/cork_early_writes",
    ),
    _Counter(
        "socket_checks", "mqtt_tpu_socket_checks_total",
        "Times the encode-once fan-out read a socket's readiness: "
        "once a delivery, or once a completion slice for a socket "
        "the slice corked",
        "/broker/egress/socket_checks",
    ),
    _Counter(
        "share_groups", "mqtt_tpu_share_groups_total",
        "Shared subscription groups a publish's fan-out selected "
        "a member of",
        "/broker/fanout/share_groups",
    ),
    _Counter(
        "share_picks_offline", "mqtt_tpu_share_picks_offline_total",
        "Selected shared-group members with no connected client "
        "(an offline session was chosen: nothing is written)",
        "/broker/fanout/share_picks_offline",
    ),
    _Counter(
        "share_select_ns", "mqtt_tpu_share_select_seconds_total",
        "Time of shared-group selection while a profiler session was "
        "live: forced materialization, the hooks' pick, select and merge",
        "/broker/fanout/share_select_ns",
        scale=1_000_000_000,
    ),
    _Counter(
        "share_groups_early", "mqtt_tpu_share_groups_early_total",
        "Shared subscription groups a completion slice picked a "
        "member of before it read its targets",
        "/broker/fanout/share_groups_early",
    ),
    _Counter(
        "qos_begins", "mqtt_tpu_qos_begins_total",
        "QoS>0 deliveries of the encode-once fan-out that reached "
        "the inflight bookkeeping",
        "/broker/fanout/qos_begins",
    ),
    _Counter(
        "qos_begins_hooked", "mqtt_tpu_qos_begins_hooked_total",
        "Of those, the ones that dispatched on_qos_publish (a hook "
        "provides it)",
        "/broker/fanout/qos_begins_hooked",
    ),
    _Counter(
        "qos_begin_ns", "mqtt_tpu_qos_begin_seconds_total",
        "Time of the encode-once fan-out's QoS>0 bookkeeping while a "
        "profiler session was live: the entry's clone, the inflight "
        "store, the hooks",
        "/broker/fanout/qos_begin_ns",
        scale=1_000_000_000,
    ),
    _Counter(
        "qos_runs", "mqtt_tpu_qos_runs_total",
        "Runs of one socket's QoS>0 deliveries in a completion slice "
        "whose inflight bookkeeping was settled at once",
        "/broker/fanout/qos_runs",
    ),
    _Counter(
        "qos_run_entries", "mqtt_tpu_qos_run_entries_total",
        "QoS>0 deliveries whose bookkeeping a run settled (the rest "
        "were begun one at a time)",
        "/broker/fanout/qos_run_entries",
    ),
    _Counter(
        "housekeep_ticks", "mqtt_tpu_housekeep_ticks_total",
        "Housekeeping ticks: the once-a-second expiry and eviction passes",
        "/broker/housekeep/ticks",
    ),
    _Counter(
        "housekeep_visits", "mqtt_tpu_housekeep_visits_total",
        "Sessions the housekeeping passes looked at: those holding "
        "in-flight entries, disconnected ones awaiting expiry, and those "
        "with a transport or queue backlog",
        "/broker/housekeep/visits",
    ),
    _Counter(
        "housekeep_ns", "mqtt_tpu_housekeep_seconds_total",
        "Time of the housekeeping ticks' expiry and eviction passes while "
        "a profiler session was live",
        "/broker/housekeep/ns",
        scale=1_000_000_000,
    ),
    _Counter(
        "sys_publish_ns", "mqtt_tpu_sys_publish_seconds_total",
        "Time of the housekeeping tick's $SYS publish while a profiler "
        "session was live",
        "/broker/housekeep/sys_publish_ns",
        scale=1_000_000_000,
    ),
)


class Server:
    """An MQTT broker server; create via ``Server(options)``
    (server.go:135-205)."""

    def __init__(self, options: Optional[Options] = None) -> None:
        opts = options or Options()
        opts.ensure_defaults()
        self.options = opts
        # ensure_defaults() guarantees a logger; the fallback keeps the
        # attribute non-Optional for every `self.log.<level>` call site
        self.log: logging.Logger = opts.logger or logging.getLogger("mqtt_tpu")
        self.info = Info(version=VERSION, started=int(time.time()))  # brokerlint: ok=R3 $SYS start stamp is wall-clock; uptime uses the monotonic anchor
        self.clients = Clients()
        # the sessions a housekeeping pass has something to look at
        # (clients.SessionsDue), kept as the registry and the sessions
        # change
        self._due = self.clients.due = SessionsDue()
        self.topics = TopicsIndex()
        self.listeners = Listeners()
        self.hooks = Hooks(self.log)
        self.will_delayed = PacketStore()
        self.done = asyncio.Event()
        self._event_loop_task: Optional[asyncio.Task] = None
        self.inline_client: Optional[Client] = None
        self._ops = _Ops(opts, self.info, self.hooks, self.log)
        self._ops.fast_publish = self.try_fast_publish
        self._ops.fast_publish_eligible = self.fast_publish_eligible
        self._ops.ingest_run = self.ingest_run
        self._ops.ack_run = self.ack_run
        self._ops.qos_run = self._settle_run
        # "no hook provides any of these events", by gate name, as of a
        # hooks generation: (generation, verdict) (_no_hook_provides)
        self._hook_gates: dict = {}
        if opts.scan_coalesce:
            # read-side decode batching: frame scans from read loops that
            # wake in the same event-loop tick coalesce into one native
            # multi-buffer call (clients.ScanGate)
            from .clients import ScanGate

            self._ops.scan_gate = ScanGate()
        self._fastpub_plans: dict = {}  # topic -> (trie version, fan-out plan)
        # event-loop shard fabric (mqtt_tpu.shards); None = single loop.
        # Built in serve() when Options.loop_shards > 1.
        self._fabric: Optional[Any] = None
        # the loop serve() ran on — the housekeeping tick's loop; under
        # the fabric, clients owned by it (or by no loop) are swept here
        self._main_loop: Optional[asyncio.AbstractEventLoop] = None
        # clients_connected gates maximum_clients: under the fabric the
        # attach/detach paths run on many shard loops, and a bare += on
        # the gauge could drift past the cap
        self._conn_lock = threading.Lock()
        # (timestamp, {loop: queued}) memo so one scrape's N per-shard
        # backlog gauges share a single client-registry walk
        self._shard_backlog_memo: Optional[tuple] = None
        # multi-core worker fabric (mqtt_tpu.cluster); None = single process
        self._cluster: Optional[Any] = None
        # set at the top of close(): CONNECTs arriving mid-drain are
        # refused with CONNACK 0x89 Server Busy instead of 0x97
        self._draining = False
        # the optional planes below stay Any-typed deliberately: each is
        # a lazily imported subsystem (device matcher, staging loop,
        # governor, telemetry/tracing) whose concrete class
        # never crosses this module's annotated signatures
        self.matcher: Optional[Any] = None  # device matcher; None = host walk
        self._stage: Optional[Any] = None  # publish staging loop (serve())
        # every parked publish names this one object as its completion:
        # the stage groups a batch's entries by it (staging._hand_over)
        self._staged_completion = self._complete_staged
        self._jax_trace_active = False  # trace_jax_profiler_dir capture
        # broker-wide overload governor (mqtt_tpu.overload): admission,
        # backpressure, and graceful shedding under publish storms.
        # Default on; the staging signal attaches in serve(), the
        # cluster signal in Cluster.__init__.
        self.overload: Optional[Any] = None
        self._outbound_backlog = 0  # last sweep's aggregate (gauge)
        # unified telemetry plane (mqtt_tpu.telemetry): stage clocks,
        # histograms, /metrics exposition, $SYS tree, flight recorder
        self.telemetry: Optional[Any] = None
        # trace plane (mqtt_tpu.tracing): span ring + device profiler
        self.tracer: Optional[Any] = None
        self.profiler: Optional[Any] = None
        # the lock-contention plane is armed in serve()
        self._lock_plane_armed = False
        if opts.telemetry:
            from .telemetry import Telemetry

            self.telemetry = Telemetry(
                sample=opts.telemetry_sample,
                ring=opts.telemetry_ring,
                dump_dir=opts.telemetry_dump_dir,
                dump_min_interval_s=opts.telemetry_dump_min_interval_ms / 1e3,
            )
            self._ops.telemetry = self.telemetry
            self._register_core_gauges()
            if opts.trace and opts.trace_sample > 0:
                from .tracing import Tracer

                self.tracer = Tracer(
                    sample=opts.trace_sample,
                    ring=opts.trace_ring,
                    registry=self.telemetry.registry,
                )
                self.tracer.adopt_max_per_s = opts.trace_adopt_max_per_s
                self.telemetry.attach_tracer(
                    self.tracer, exemplars=opts.trace_exemplars
                )
            if opts.profile_locks:
                # export the per-lock wait/hold families now; ARMING
                # waits for serve() so a constructed-but-never-served
                # Server (embedder probes, test harnesses) costs nothing
                from .utils.locked import DEFAULT_PLANE

                self.telemetry.attach_lock_plane(DEFAULT_PLANE)
        # cluster-wide SLO observatory (ISSUE 14, mqtt_tpu.slo): the
        # delivery-latency SLI gate plus the burn-rate engine when
        # objectives are declared; evaluate() rides the housekeeping tick
        self.slo: Optional[Any] = None
        # per-device observability plane (ISSUE 18); built further down
        # once the matcher + device profiler exist to attach
        self.device_stats: Optional[Any] = None
        if self.telemetry is not None:
            self.telemetry.delivery_sli = bool(opts.slo)
            if opts.slo and opts.slo_objectives:
                from .slo import SLOEngine, parse_objectives

                objectives = parse_objectives(opts.slo_objectives)
                if objectives:
                    self.slo = SLOEngine(
                        self.telemetry,
                        objectives,
                        burn_threshold=opts.slo_burn_threshold,
                        publish=self._publish_slo_transition,
                    )
                    self.telemetry.attach_slo(self.slo)
        if opts.overload_control:
            from .overload import OverloadConfig, OverloadGovernor

            self.overload = OverloadGovernor(
                OverloadConfig(
                    throttle_enter=opts.overload_throttle_enter,
                    throttle_exit=opts.overload_throttle_exit,
                    shed_enter=opts.overload_shed_enter,
                    shed_exit=opts.overload_shed_exit,
                    min_dwell_s=opts.overload_min_dwell_ms / 1e3,
                    eval_interval_s=opts.overload_eval_interval_ms / 1e3,
                    quota_window_s=opts.overload_quota_window_ms / 1e3,
                    publish_quota=opts.overload_publish_quota,
                    throttle_delay_s=opts.overload_throttle_delay_ms / 1e3,
                    shed_quota=opts.overload_shed_quota,
                    eviction_grace_s=opts.overload_eviction_grace_ms / 1e3,
                    admission_reserve=opts.overload_admission_reserve,
                    priority_weights=dict(opts.overload_priority_classes or {}),
                )
            )
            self._ops.overload = self.overload
            self.overload.add_source("outbound", self._outbound_pressure)
            if opts.overload_memory_limit_mb > 0:
                limit = opts.overload_memory_limit_mb * 1024 * 1024
                self.overload.add_source(
                    "memory", lambda: rss_bytes() / limit
                )
        # MQTT+ payload-predicate plane (mqtt_tpu.predicates): suffix
        # registry + host interpreter + device rule table. Built before
        # the matcher so the staging loop can carry its feature batches.
        self._predicates: Optional[Any] = None
        if opts.predicate_filters:
            from .predicates import PredicateEngine

            self._predicates = PredicateEngine(
                max_rules=opts.predicate_max_rules,
                oracle_sample=opts.predicate_oracle_sample,
                registry=(
                    self.telemetry.registry
                    if self.telemetry is not None
                    else None
                ),
            )
        # secure multi-tenant plane (mqtt_tpu.tenancy): tenant registry +
        # CONNECT resolution + the MQT-TZ re-encryption engine. Built
        # before the matcher so the staging loop can carry decrypt jobs.
        self._tenancy: Optional[Any] = None
        self._recrypt: Optional[Any] = None
        if opts.tenancy:
            from .tenancy import RecryptEngine, TenantPlane

            self._tenancy = TenantPlane(
                registry=(
                    self.telemetry.registry
                    if self.telemetry is not None
                    else None
                )
            )
            self._tenancy.configure(
                opts.tenants, opts.tenant_users, opts.tenant_default
            )
            if opts.recrypt:
                self._recrypt = RecryptEngine(
                    self._tenancy.keys,
                    oracle_sample=opts.recrypt_oracle_sample,
                    device_min_blocks=opts.recrypt_device_min_blocks,
                    registry=(
                        self.telemetry.registry
                        if self.telemetry is not None
                        else None
                    ),
                )
        # device-resident retained matching (ISSUE 16, mqtt_tpu.ops.
        # retained): wildcard-SUBSCRIBE fan-out over the retained corpus
        # served by the flat kernel run in reverse, host walk as 1-in-N
        # oracle behind its own breaker. Opt-in; None = host walk only.
        self._retained_engine: Optional[Any] = None
        if opts.retained_matcher:
            from .ops.retained import RetainedMatchEngine

            self._retained_engine = RetainedMatchEngine(
                self.topics,
                oracle_sample=opts.retained_oracle_sample,
            )
        # durable session plane recovery state (read_store / healthz /
        # $SYS/broker/durable): `recovering` holds /healthz at 503 until
        # the restored maps are actually served
        self._durable: dict = {
            "recovering": False,
            "recovery_seconds": 0.0,
            # the one match-table build the restore's bulk load ended in
            # (DeltaMatcher.bulk_build_seconds; 0 with no device matcher)
            "restore_build_seconds": 0.0,
            "replayed_keys": 0,
            "restored_subscriptions": 0,
            "restored_retained": 0,
            "restored_inflight": 0,
            "restore_batches": 0,
        }
        if opts.device_matcher:
            from .ops.delta import DeltaMatcher

            # the pair-buffer capacity rides beside matcher_opts (which
            # wins on conflict)
            mopts: dict = {"compact_capacity": opts.matcher_compact_capacity}
            mopts.update(opts.matcher_opts or {})
            self.matcher = DeltaMatcher(self.topics, **mopts)
            if opts.matcher_resilience:
                # degradation manager (mqtt_tpu.resilience): breaker +
                # hang watchdog + half-open probes around every dispatch
                from .resilience import BreakerConfig, ResilientMatcher

                self.matcher = ResilientMatcher(
                    self.matcher,
                    self.topics,
                    BreakerConfig(
                        failure_threshold=opts.breaker_failure_threshold,
                        watchdog_s=opts.breaker_watchdog_ms / 1e3,
                        probe_backoff_s=opts.breaker_probe_backoff_ms / 1e3,
                        probe_backoff_max_s=(
                            opts.breaker_probe_backoff_max_ms / 1e3
                        ),
                        probe_jitter=opts.breaker_probe_jitter,
                        probe_successes=opts.breaker_probe_successes,
                        verify_sample=opts.breaker_verify_sample,
                    ),
                )
        if self.telemetry is not None:
            # degradation triggers dump the flight recorder: entering SHED
            # (overload storm) and a breaker trip (device failure) both
            # leave a JSON trace of the publishes that led up to them
            if self.overload is not None:
                self.overload.on_transition = self._overload_transition
            if self.matcher is not None:
                stats = getattr(self.matcher, "stats", None)
                if stats is not None:
                    # compile/rebuild/fold wall times -> rebuild histogram
                    stats.rebuild_observer = self.telemetry.rebuild_hist.observe
                if self.tracer is not None:
                    # device pipeline profiler (mqtt_tpu.tracing): the
                    # innermost matcher feeds its dispatch/D2H windows
                    # into duty-cycle / overlap / idle-gap accounting,
                    # and the staging drain loop reads the same object
                    # to sub-stamp sampled traces
                    from .tracing import DeviceProfiler

                    self.profiler = DeviceProfiler(
                        registry=self.telemetry.registry
                    )
                    snap = getattr(self.matcher, "_snap", None)
                    if snap is not None and hasattr(snap, "profiler"):
                        snap.profiler = self.profiler
                    # what its slice snapshots read, and who feeds it
                    # besides the staging loop: the read loops and
                    # fan-out (per-publish loop counters while a
                    # jax.profiler session is live); its own session
                    # follower (started in serve()) polls it, so a
                    # session that ends after the traffic stopped
                    # still closes the slice
                    self.profiler.matcher_stats = stats
                    self.profiler.counters = self._slice_counters
                    self._ops.profiler = self.profiler
                # mesh-sharded snapshot: per-shard compile times land in
                # shard-local histograms on the rebuild path; the scrape
                # merges them on demand (telemetry callback histogram)
                snap = getattr(self.matcher, "_snap", None)
                merged = getattr(snap, "merged_shard_compile", None)
                if merged is not None:
                    self.telemetry.registry.histogram(
                        "mqtt_tpu_matcher_shard_compile_seconds",
                        "Per-shard flat-index compile wall time (shard-local "
                        "histogram shards, merged at scrape)",
                        fn=merged,
                    )
                breaker = getattr(self.matcher, "breaker", None)
                if breaker is not None:
                    prev_trip = breaker.on_trip

                    def _trip_dump(_prev=prev_trip):
                        # fires AFTER the breaker lock is released
                        # (_fire_on_trip, brokerlint R5) — confirmed by
                        # the lock witness: no matcher_breaker ->
                        # flight_ring edge exists at runtime
                        if _prev is not None:
                            _prev()
                        self.telemetry.trigger_dump(
                            "breaker_trip", {"trigger": "matcher_breaker"}
                        )

                    breaker.on_trip = _trip_dump
            # per-device observability plane (ISSUE 18, ops/devicestats):
            # HBM gauges + the compile-event ledger + the shard-skew
            # gauge; adopts the device profiler's per-device windows and
            # the sharded snapshot's tile-skew state when they exist
            if opts.device_stats:
                from .ops.devicestats import DeviceStatsPlane

                # only a broker that keeps state on the device may
                # enumerate (= initialize) the backend: a chip belongs
                # to one process, so a host-only worker beside a
                # device-matcher broker gets the ledger-only plane
                plane = DeviceStatsPlane(
                    registry=self.telemetry.registry,
                    hbm_watermark=opts.device_hbm_watermark,
                    devices=(
                        None
                        if self.matcher is not None
                        or self._retained_engine is not None
                        else []
                    ),
                )
                if self.profiler is not None:
                    plane.attach_profiler(self.profiler)
                for cand in (
                    getattr(self.matcher, "_snap", None),
                    self.matcher,
                ):
                    if cand is not None and hasattr(cand, "device_skew_ratio"):
                        plane.attach_matcher(cand)
                        break
                self.telemetry.attach_device_stats(plane)
                self.device_stats = plane
            if self._recrypt is not None:
                rbreaker = self._recrypt.breaker
                prev_rtrip = rbreaker.on_trip

                def _recrypt_trip_dump(_prev=prev_rtrip):
                    # fires AFTER the breaker lock is released
                    # (_fire_on_trip, brokerlint R5) — a failing crypto
                    # device leaves a flight-recorder trace, exactly
                    # like the matcher and predicate breakers
                    if _prev is not None:
                        _prev()
                    self.telemetry.trigger_dump(
                        "breaker_trip", {"trigger": "recrypt_breaker"}
                    )

                rbreaker.on_trip = _recrypt_trip_dump
            # durable session plane + retained-match engine observability
            # (ISSUE 16): recovery progress, log-store internals, and the
            # device-vs-host retained oracle all surface on /metrics
            self._register_durable_metrics()
        if opts.inline_client:
            self.inline_client = self.new_client(None, None, LOCAL_LISTENER, INLINE_CLIENT_ID, True)
            self.clients.add_client(self.inline_client)

    # -- construction ------------------------------------------------------

    def new_client(self, reader, writer, listener: str, id_: str, inline: bool) -> Client:
        """A client wired to this server's ops (server.go:241-260)."""
        cl = Client(reader, writer, self._ops)
        cl.id = id_
        cl.net.listener = listener
        if inline:
            cl.net.inline = True
            # don't restrict embedding-application publishes by default
            cl.state.inflight.reset_receive_quota((1 << 31) - 1)
        return cl

    def add_hook(self, hook: Hook, config: Any = None) -> None:
        """Attach a hook, ideally before serve() (server.go:264-272)."""
        hook.set_opts(self.log, HookOptions(capabilities=self.options.capabilities))
        self.log.info("added hook %s", hook.id())
        self.hooks.add(hook, config)

    def add_listener(self, listener: Listener) -> None:
        """Register a listener; init happens during serve (server.go:286-301)."""
        if self.listeners.get(listener.id()) is not None:
            raise ListenerIDExistsError(listener.id())
        self.listeners.add(listener)

    def _listener_from_config(self, conf: ListenerConfig) -> Optional[Listener]:
        t = conf.type.lower()
        if t == TYPE_TCP:
            return TCP(conf)
        if t == TYPE_MOCK:
            return MockListener(conf.id, conf.address)
        if t in (TYPE_WS, TYPE_UNIX, TYPE_HEALTHCHECK, TYPE_SYSINFO):
            # built-in extra listeners are registered lazily to avoid import
            # cycles; they live in mqtt_tpu.listeners.*
            from . import listeners as lmod

            builders = {
                TYPE_WS: getattr(lmod, "Websocket", None),
                TYPE_UNIX: getattr(lmod, "UnixSock", None),
                TYPE_HEALTHCHECK: getattr(lmod, "HTTPHealthCheck", None),
                TYPE_SYSINFO: getattr(lmod, "HTTPStats", None),
            }
            builder = builders.get(t)
            if builder is not None:
                if t == TYPE_SYSINFO:
                    # the stats listener also serves GET /metrics when
                    # the telemetry plane is on (mqtt_tpu.telemetry),
                    # plus /healthz, /metrics/cluster and /cluster/slo
                    # (ISSUE 14 — the SLO observatory's scrape surfaces)
                    return builder(
                        conf, self.info, self.telemetry,
                        health=self.health_report,
                    )
                return builder(conf)
        self.log.error("listener type unavailable by config: %s", conf.type)
        return None

    def add_listeners_from_config(self, configs: list[ListenerConfig]) -> None:
        for conf in configs:
            listener = self._listener_from_config(conf)
            if listener is not None:
                self.add_listener(listener)

    # -- lifecycle ---------------------------------------------------------

    async def serve(self) -> None:
        """Start hooks, restore persisted state, init+serve all listeners,
        begin the housekeeping loop (server.go:334-371)."""
        self.log.info("mqtt_tpu starting version=%s", VERSION)
        if self.options.gc_tuning:
            # process-global: embedders opt out via Options.gc_tuning
            from .utils.gctune import tune_for_throughput

            tune_for_throughput()
            self.log.info(
                "gc thresholds tuned for broker throughput "
                "(Options.gc_tuning=False restores the application's cadence)"
            )
        # warm the native core now — its first-use lazy compile would
        # otherwise block the event loop mid-connection
        from .native import available as _native_available

        await asyncio.get_running_loop().run_in_executor(None, _native_available)
        if self.options.listeners:
            self.add_listeners_from_config(self.options.listeners)
        for hook, config in self.options.hooks:
            self.add_hook(hook, config)

        if self.hooks.provides(
            STORED_CLIENTS,
            STORED_INFLIGHT_MESSAGES,
            STORED_RETAINED_MESSAGES,
            STORED_SUBSCRIPTIONS,
            STORED_SYS_INFO,
        ):
            self.read_store()

        if self.matcher is not None:
            budget_ms = self.options.matcher_stage_latency_budget_ms
            self._stage = MatchStage(
                self.matcher,
                host_fallback=self.topics.subscribers,
                window_s=self.options.matcher_stage_window_ms / 1e3,
                max_batch=self.options.matcher_stage_max_batch,
                max_inflight=self.options.matcher_stage_max_inflight,
                latency_budget_s=(budget_ms / 1e3) if budget_ms > 0 else None,
                max_pending=self.options.overload_stage_max_pending,
                telemetry=self.telemetry,
                profiler=self.profiler,
                predicates=self._predicates,
                pipeline_depth=self.options.matcher_stage_pipeline_depth,
                recrypt=self._recrypt,
            )
            self._stage.start()
            if self.overload is not None:
                self.overload.add_source("staging", self._stage.pressure)
            if self.options.trace_jax_profiler_dir:
                # deep-dive capture hook (mqtt_tpu.tracing): the host-side
                # duty-cycle numbers say WHETHER the device idles; a
                # jax.profiler trace says WHY. Failure to start must
                # never block serving.
                try:
                    import jax

                    jax.profiler.start_trace(
                        self.options.trace_jax_profiler_dir
                    )
                    self._jax_trace_active = True
                    self.log.info(
                        "jax.profiler trace started (dir=%s)",
                        self.options.trace_jax_profiler_dir,
                    )
                except Exception:
                    self.log.exception("jax.profiler trace failed to start")

        if self.profiler is not None:
            # the session follower is a daemon that takes no broker
            # lock, so it starts before traffic and runs for the
            # broker's life
            self.profiler.start()
        if (
            self.telemetry is not None
            and self.telemetry.lock_plane is not None
            and not self._lock_plane_armed
        ):
            # arm the lock-contention plane for this broker's lifetime
            # (refcounted: concurrent in-process brokers cannot disarm
            # each other; close() releases this server's hold)
            self.telemetry.lock_plane.arm()
            self._lock_plane_armed = True
        self._main_loop = asyncio.get_running_loop()
        if self.options.loop_shards > 1:
            # event-loop shard fabric (mqtt_tpu.shards / ROADMAP item
            # 4): built before listener init so stream listeners bind
            # raw fabric sockets instead of main-loop asyncio servers
            from .listeners import StreamListener
            from .shards import ShardFabric

            self._fabric = ShardFabric(self.options.loop_shards, server=self)
            reuseport = self.options.loop_shard_accept == "reuseport"
            for lst in self.listeners.internal.values():
                if isinstance(lst, StreamListener):
                    lst.attach_fabric(self._fabric, reuseport=reuseport)
            self._fabric.start()
            if self.telemetry is not None:
                self._fabric.register_metrics(self.telemetry.registry)
            self.log.info(
                "event-loop shard fabric started: shards=%d accept=%s",
                self.options.loop_shards,
                self.options.loop_shard_accept,
            )
        for listener in list(self.listeners.internal.values()):
            await listener.init(self.log)
        self._event_loop_task = asyncio.get_running_loop().create_task(self._event_loop())
        await self.listeners.serve_all(self.establish_connection)
        self.publish_sys_topics()
        self.hooks.on_started()
        if self._durable["recovering"]:
            flush = getattr(self.matcher, "flush", None)
            if flush is not None:
                # the restore was one bulk load of the trie, and its
                # close woke the matcher for ONE build of the restored
                # table: wait for that build (flush() queues behind it,
                # or does it if the thread has not yet) off the loop,
                # which keeps answering from the host trie meanwhile
                await asyncio.get_running_loop().run_in_executor(None, flush)
                self._durable["restore_build_seconds"] = getattr(
                    self.matcher, "bulk_build_seconds", 0.0
                )
            # the restored maps are now actually served: flip healthz
            # from 503 `recovering` to ready and leave the recovery
            # numbers behind as retained $SYS/broker/durable/# rows
            self._durable["recovering"] = False
            self.publish_durable_sys()
            self.log.info(
                "durable restore complete: seconds=%.3f build_seconds=%.3f "
                "replayed_keys=%d subscriptions=%d retained=%d inflight=%d "
                "batches=%d",
                self._durable["recovery_seconds"],
                self._durable["restore_build_seconds"],
                self._durable["replayed_keys"],
                self._durable["restored_subscriptions"],
                self._durable["restored_retained"],
                self._durable["restored_inflight"],
                self._durable["restore_batches"],
            )
        self.log.info("mqtt_tpu server started")

    async def _event_loop(self) -> None:
        """Housekeeping ticks (server.go:374-395): expiry reaping every
        second, $SYS publishing on its own interval."""
        sys_interval = self.options.sys_topic_resend_interval
        next_sys = time.monotonic() + sys_interval
        while not self.done.is_set():
            try:
                await asyncio.wait_for(self.done.wait(), timeout=1.0)
                return
            except asyncio.TimeoutError:
                pass
            now = int(time.time())  # brokerlint: ok=R3 expiry sweeps compare against absolute wall-clock stamps
            self.housekeep(now)
            if self.slo is not None:
                # SLO burn-rate evaluation rides the housekeeping tick
                # (mqtt_tpu.slo): a handful of histogram-children walks
                # per second, transitions publish $SYS + dump from here
                # (the event-loop context the $SYS publisher requires)
                try:
                    self.slo.evaluate()
                except Exception:
                    self.log.exception("SLO evaluation failed")
            if time.monotonic() >= next_sys:
                prof = self.profiler
                if prof is not None and prof.armed:
                    t0 = time.perf_counter_ns()
                    with prof.annotation("mqtt/sys.publish"):
                        self.publish_sys_topics()
                    t1 = time.perf_counter_ns()
                    self._ops.sys_publish_ns += t1 - t0
                    prof.note_host_span("mqtt/sys.publish", t0, t1)
                else:
                    self.publish_sys_topics()
                next_sys = time.monotonic() + sys_interval

    def housekeep(self, now: int) -> None:
        """One tick's expiry and eviction passes, each over the sessions
        that can have something due (``SessionsDue``): counted and, while
        a profiler session is live, timed and a ``mqtt/housekeep`` span
        (``DeviceProfiler.note_host_span``)."""
        ops = self._ops
        prof = self.profiler
        if prof is not None and prof.armed:
            visits = ops.housekeep_visits
            t0 = time.perf_counter_ns()
            with prof.annotation("mqtt/housekeep"):
                self._housekeep_passes(now)
            t1 = time.perf_counter_ns()
            ops.housekeep_ns += t1 - t0
            prof.note_host_span(
                "mqtt/housekeep", t0, t1, visits=ops.housekeep_visits - visits
            )
        else:
            self._housekeep_passes(now)
        ops.housekeep_ticks += 1

    def _housekeep_passes(self, now: int) -> None:
        self.clear_expired_clients(now)
        self.clear_expired_retained_messages(now)
        self.send_delayed_lwt(now)
        self.clear_expired_inflights(now)
        self.sweep_overload()

    # -- telemetry plane (mqtt_tpu.telemetry) ------------------------------

    @staticmethod
    def _view_materializations() -> int:
        """The C view module's materialization count (0 sans toolchain)."""
        from .ops.matcher import _accel

        acc = _accel()
        if acc is None or not hasattr(acc, "view_stats"):
            return 0
        return acc.view_stats()["materializations"]

    def _slice_counters(self) -> dict:
        """The broker's running counts a profiler slice's snapshots take
        (``tracing.DeviceProfiler.counters``): fallbacks the stage held
        in their publisher's order, frames handed to subscribers'
        sockets, every ``_Ops`` count (``_OPS_COUNTERS``), the lazy
        results forced into dicts (the C view module's count), the
        matcher's wide entries and the topics they answered, what the
        trie holds (``TopicsIndex``'s three counts), and what set-up's
        load cost, as values at the snapshot: the bulk loads'
        open-to-close wall and the build that ended the newest one."""
        stage = self._stage
        trie = self.topics
        stats = None if self.matcher is None else self.matcher.stats
        return {
            "order_held": 0 if stage is None else stage.order_held,
            "deliveries": self.telemetry.fanout_deliveries.value,
            **{c.attr: getattr(self._ops, c.attr) for c in _OPS_COUNTERS},
            "view_materializations": self._view_materializations(),
            "wide_entries": getattr(stats, "wide_entries", 0),
            "wide_topics": getattr(stats, "wide_topics", 0),
            "particles": trie.particles,
            "particle_maps": trie.particle_maps,
            "held": trie.held,
            "bulk_load_seconds": trie.bulk_load_seconds,
            "bulk_build_seconds": getattr(self.matcher, "bulk_build_seconds", 0.0),
        }

    def _register_core_gauges(self) -> None:
        """Scrape-time gauges over state other layers already maintain:
        the $SYS Info counters, matcher stats, and governor posture all
        surface on /metrics without a second bookkeeping path."""
        r = self.telemetry.registry
        info = self.info
        # monotonic Info fields export as callback-backed COUNTERS: the
        # _total suffix promises counter semantics (rate()/increase(),
        # reset detection) and OpenMetrics linting rejects _total gauges
        for name, attr in (
            ("mqtt_tpu_messages_received_total", "messages_received"),
            ("mqtt_tpu_messages_sent_total", "messages_sent"),
            ("mqtt_tpu_messages_dropped_total", "messages_dropped"),
            ("mqtt_tpu_packets_received_total", "packets_received"),
            ("mqtt_tpu_packets_sent_total", "packets_sent"),
            ("mqtt_tpu_bytes_received_total", "bytes_received"),
            ("mqtt_tpu_bytes_sent_total", "bytes_sent"),
        ):
            r.counter(
                name, f"$SYS mirror of Info.{attr}", fn=lambda a=attr: getattr(info, a)
            )
        for name, attr in (
            ("mqtt_tpu_clients_connected", "clients_connected"),
            ("mqtt_tpu_subscriptions", "subscriptions"),
            ("mqtt_tpu_retained_messages", "retained"),
            ("mqtt_tpu_inflight_messages", "inflight"),
        ):
            r.gauge(name, f"$SYS mirror of Info.{attr}", fn=lambda a=attr: getattr(info, a))
        for name, attr, what in (
            (
                "mqtt_tpu_topics_particles",
                "particles",
                "Live nodes of the topic trie (the root is one)",
            ),
            (
                "mqtt_tpu_topics_particle_maps",
                "particle_maps",
                "Containers alive across the trie's nodes: children dicts "
                "and subscription, shared and inline maps (a node makes one "
                "with its first entry of the kind and drops it with the last)",
            ),
            (
                "mqtt_tpu_topics_held",
                "held",
                "Subscriptions of all three kinds the trie holds, bulk-loaded "
                "ones included (mqtt_tpu_subscriptions counts live clients')",
            ),
        ):
            r.gauge(name, what, fn=lambda a=attr: getattr(self.topics, a))
        r.gauge(
            "mqtt_tpu_uptime_seconds",
            "Monotonic seconds since broker start (clock-step immune)",
            fn=info.uptime_now,
        )
        r.gauge(
            "mqtt_tpu_overload_state_code",
            "Overload governor posture (0=normal 1=throttle 2=shed)",
            fn=lambda: (
                0 if self.overload is None else self.overload.gauges()["state_code"]
            ),
        )
        r.gauge(
            "mqtt_tpu_overload_pressure",
            "Max normalized pressure across governor signals",
            fn=lambda: 0.0 if self.overload is None else self.overload.pressure,
        )
        r.gauge(
            "mqtt_tpu_stage_pending_depth",
            "Publishes parked in the staging loop",
            fn=lambda: 0 if self._stage is None else self._stage.pending_depth,
        )
        # how publishes leave the stage (mqtt_tpu.staging): through their
        # batch's completion call (the served path: no task, future or
        # coroutine a publish) or through submit()'s future (tests and
        # embedders: 0 on the served path), and the completion calls
        # made (one a slice of a batch: one read of the client registry)
        for path, attr in (("batch", "batch_completed"), ("adapter", "adapter_completed")):
            r.counter(
                "mqtt_tpu_stage_completed_total",
                "Publishes completed by the staging loop, by path: their "
                "batch's completion call, or submit()'s future adapter",
                fn=lambda attr=attr: (
                    0 if self._stage is None else getattr(self._stage, attr)
                ),
                path=path,
            )
        r.counter(
            "mqtt_tpu_stage_completion_calls_total",
            "Batch completion calls made by the staging loop (one a slice "
            "of a batch)",
            fn=lambda: (
                0 if self._stage is None else self._stage.batch_completions
            ),
        )
        r.counter(
            "mqtt_tpu_stage_order_held_total",
            "Staging fallbacks (admission, issue_error) that joined their "
            "publisher's order as held members instead of completing at once",
            fn=lambda: 0 if self._stage is None else self._stage.order_held,
        )
        for c in _OPS_COUNTERS:
            register = r.gauge if c.kind == "gauge" else r.counter
            if c.scale == 1:
                register(c.metric, c.help, fn=lambda a=c.attr: getattr(self._ops, a))
            else:
                register(
                    c.metric,
                    c.help,
                    fn=lambda a=c.attr, k=c.scale: getattr(self._ops, a) / k,
                )
        r.gauge(
            "mqtt_tpu_staging_pipeline_depth",
            "Device batches in flight across the staging pipeline legs",
            fn=lambda: (
                0 if self._stage is None else self._stage.inflight_batches
            ),
        )
        # zero-materialization fan-out (ISSUE 13): how often a lazy
        # SubscribersView was forced into the eager dicts (any dict-
        # semantics consumer — shared groups, predicates, differential
        # verification). Near zero on the pure client fan-out path.
        r.counter(
            "mqtt_tpu_fanout_view_materializations_total",
            "Lazy fan-out views forced into materialized Subscribers "
            "dicts (the C view module's own count)",
            fn=self._view_materializations,
        )
        r.counter(
            "mqtt_tpu_staging_compact_overflow_total",
            "Batches whose compacted hits outgrew the pair buffer and "
            "fell back to the padded path (MatcherStats.compact_overflows)",
            fn=lambda: (
                0
                if self.matcher is None
                else getattr(self.matcher.stats, "compact_overflows", 0)
            ),
        )
        r.gauge(
            "mqtt_tpu_outbound_backlog",
            "Aggregate publishes parked in client outbound queues "
            "(last overload-sweep sample)",
            fn=lambda: self._outbound_backlog,
        )
        r.gauge(
            "mqtt_tpu_fanout_amplification_ratio",
            "Outbound PUBLISH encodes per inbound PUBLISH — the "
            "per-subscriber re-encode waste (ROADMAP item 3)",
            fn=lambda: (
                self.telemetry.publish_encodes.value
                / max(1, info.messages_received)
            ),
        )
        for name, field_ in (
            ("mqtt_tpu_matcher_batches_total", "batches"),
            ("mqtt_tpu_matcher_topics_total", "topics"),
            ("mqtt_tpu_matcher_host_fallbacks_total", "host_fallbacks"),
            ("mqtt_tpu_matcher_overflows_total", "overflows"),
            ("mqtt_tpu_matcher_rebuilds_total", "rebuilds"),
            ("mqtt_tpu_matcher_folds_total", "folds"),
            ("mqtt_tpu_matcher_bulk_loads_total", "bulk_loads"),
            ("mqtt_tpu_matcher_rebuilds_held_total", "rebuilds_held"),
            ("mqtt_tpu_matcher_host_fast_total", "host_fast"),
            ("mqtt_tpu_matcher_compact_batches_total", "compact_batches"),
            ("mqtt_tpu_matcher_d2h_bytes_total", "d2h_bytes"),
            ("mqtt_tpu_matcher_wide_topics_total", "wide_topics"),
        ):
            r.counter(
                name,
                f"MatcherStats.{field_} (0 when no device matcher)",
                fn=lambda f=field_: (
                    0
                    if self.matcher is None
                    else getattr(self.matcher.stats, f, 0)
                ),
            )
        r.gauge(
            "mqtt_tpu_matcher_wide_entries",
            "Entries of the served index that more subscribers hold than "
            "the window (MatcherStats.wide_entries; 0 when no device matcher)",
            fn=lambda: (
                0
                if self.matcher is None
                else getattr(self.matcher.stats, "wide_entries", 0)
            ),
        )

    def _durable_store_stats(self) -> dict:
        """Merge ``durable_stats()`` across storage hooks that expose one
        (duck-typed — the LogKV store does; third-party hooks may too)."""
        out: dict = {}
        for hook in self.hooks.get_all():
            fn = getattr(hook, "durable_stats", None)
            if not callable(fn):
                continue
            try:
                stats = fn()
            except Exception:  # pragma: no cover  # brokerlint: ok=R4 observability merge must not take the broker down with a hook
                continue
            for k, v in stats.items():
                if isinstance(v, (int, float)) and not isinstance(v, bool):
                    out[k] = out.get(k, 0) + v
                else:
                    out.setdefault(k, v)
        return out

    def _register_durable_metrics(self) -> None:
        """Recovery + durable-store + retained-engine families (ISSUE 16).
        All callback-backed: scrape reads the live counters; zeros when
        no durable hook / engine is configured."""
        r = self.telemetry.registry
        r.gauge(
            "mqtt_tpu_durable_recovery_seconds",
            "Wall seconds the last restart spent restoring persisted "
            "state (store replay + bulk re-registration)",
            fn=lambda: self._durable["recovery_seconds"],
        )
        r.gauge(
            "mqtt_tpu_durable_restore_build_seconds",
            "Wall seconds of the one match-table build the last restore's "
            "bulk load ended in (0 with no device matcher)",
            fn=lambda: self._durable["restore_build_seconds"],
        )
        r.counter(
            "mqtt_tpu_durable_replayed_keys_total",
            "Keys replayed from durable-store segments/snapshots at the "
            "last restart (sum across storage hooks)",
            fn=lambda: self._durable["replayed_keys"],
        )
        r.gauge(
            "mqtt_tpu_durable_recovering",
            "1 while restored state is still being re-registered "
            "(healthz holds 503), else 0",
            fn=lambda: 1 if self._durable["recovering"] else 0,
        )
        r.counter(
            "mqtt_tpu_durable_restore_batches_total",
            "Bulk re-registration batches used by the last restore "
            "(subscriptions + retained, staging.bulk_*)",
            fn=lambda: self._durable["restore_batches"],
        )
        r.gauge(
            "mqtt_tpu_durable_segments",
            "Live log segments across durable storage hooks",
            fn=lambda: self._durable_store_stats().get("segments", 0),
        )
        r.gauge(
            "mqtt_tpu_durable_snapshot_age_seconds",
            "Seconds since the newest durable snapshot (-1 when none)",
            fn=lambda: self._durable_store_stats().get(
                "snapshot_age_seconds", -1.0
            ),
        )
        r.counter(
            "mqtt_tpu_durable_replay_corruptions_total",
            "Corrupt records hit during segment replay (CRC/frame "
            "failures — each truncates one segment's tail)",
            fn=lambda: self._durable_store_stats().get("replay_corruptions", 0),
        )
        eng = self._retained_engine
        r.counter(
            "mqtt_tpu_retained_device_matches_total",
            "Retained-topic SUBSCRIBE matches answered by the device "
            "kernel (mqtt_tpu.ops.retained)",
            fn=lambda: 0 if eng is None else eng.device_matches,
        )
        r.counter(
            "mqtt_tpu_retained_oracle_checks_total",
            "Differential host-walk oracle comparisons run by the "
            "retained-match engine",
            fn=lambda: 0 if eng is None else eng.oracle_checks,
        )
        r.counter(
            "mqtt_tpu_retained_oracle_mismatches_total",
            "Oracle comparisons where device and host disagreed (host "
            "won; breaker counted a failure)",
            fn=lambda: 0 if eng is None else eng.oracle_mismatches,
        )
        r.counter(
            "mqtt_tpu_retained_host_fallbacks_total",
            "Retained matches served by the host walk while the engine "
            "was active (depth/filter/overflow/error/breaker classes)",
            fn=lambda: 0 if eng is None else sum(eng.fallbacks.values()),
        )

    def publish_durable_sys(self) -> None:
        """Publish the recovery progress tree as retained
        ``$SYS/broker/durable/#`` rows (ISSUE 16): serve() calls this
        once the restored maps are actually served, and the periodic
        $SYS tick republishes via publish_sys_topics."""
        d = self._durable
        store = self._durable_store_stats()
        rows = {
            "recovering": "1" if d["recovering"] else "0",
            "recovery_seconds": "%.6f" % d["recovery_seconds"],
            "restore_build_seconds": "%.6f" % d["restore_build_seconds"],
            "replayed_keys": str(d["replayed_keys"]),
            "restored_subscriptions": str(d["restored_subscriptions"]),
            "restored_retained": str(d["restored_retained"]),
            "restored_inflight": str(d["restored_inflight"]),
            "restore_batches": str(d["restore_batches"]),
        }
        for k in ("segments", "snapshot_seq", "replay_corruptions", "snapshot_invalid"):
            if k in store:
                rows[k] = str(store[k])
        pk = Packet(
            fixed_header=FixedHeader(type=pkts.PUBLISH, retain=True),
            created=int(time.time()),  # brokerlint: ok=R3 $SYS stamps are wall-clock (operator-correlatable)
        )
        for name, payload in rows.items():
            pk.topic_name = SYS_PREFIX + "/broker/durable/" + name
            pk.payload = payload.encode()
            retained = pk.copy(False)
            self.topics.retain_message(retained)
            if self._retained_engine is not None:
                self._retained_engine.note_retained(retained.topic_name, True)
            self.publish_to_subscribers(pk)

    def _publish_slo_transition(self, name: str, payload: dict) -> None:
        """Publish one objective's breach/recovery as a retained
        ``$SYS/broker/slo/<name>`` message (mqtt_tpu.slo calls this on
        transitions only, from the housekeeping tick's event-loop
        context — the same path the periodic $SYS publisher uses)."""
        pk = Packet(
            fixed_header=FixedHeader(type=pkts.PUBLISH, retain=True),
            created=int(time.time()),  # brokerlint: ok=R3 $SYS transition stamps are wall-clock (operator-correlatable)
        )
        pk.topic_name = SYS_PREFIX + "/broker/slo/" + name
        pk.payload = json.dumps(payload).encode()
        self.topics.retain_message(pk.copy(False))
        if self._retained_engine is not None:
            self._retained_engine.note_retained(pk.topic_name, True)
        self.publish_to_subscribers(pk)

    def health_report(self) -> tuple[bool, dict]:
        """The ``GET /healthz`` readiness snapshot (ISSUE 14 satellite).

        503 (not ready) only for conditions under which the broker
        should be pulled from rotation: draining/shutdown, a governor
        in SHED, or a dead staging pipeline. A tripped matcher breaker
        or dark mesh edges DEGRADE (reported in the body, readiness
        holds) — the broker still serves through its fallback paths,
        and flapping a load balancer on a self-healing breaker would
        amplify the incident."""
        not_ready: list[str] = []
        degraded: list[str] = []
        detail: dict = {}
        if self._draining or self.done.is_set():
            not_ready.append("draining")
        if self._durable["recovering"]:
            # restored state is still re-registering: a load balancer
            # must not route sessions at a half-restored map
            not_ready.append("recovering")
        if self._durable["replayed_keys"] or self._durable["restore_batches"]:
            detail["durable"] = {
                "recovering": self._durable["recovering"],
                "recovery_seconds": round(
                    self._durable["recovery_seconds"], 3
                ),
                "restore_build_seconds": round(
                    self._durable["restore_build_seconds"], 3
                ),
                "replayed_keys": self._durable["replayed_keys"],
            }
        gov = self.overload
        if gov is not None:
            from .overload import SHED

            detail["governor"] = {
                "state": str(gov.state),
                "pressure": round(gov.pressure, 4),
            }
            if gov.state == SHED:
                not_ready.append("governor_shed")
        stage = self._stage
        if stage is not None:
            alive = stage.alive()
            detail["staging"] = {
                "alive": alive,
                "pending": stage.pending_depth,
                "inflight": stage.inflight_batches,
            }
            if not alive:
                not_ready.append("staging_dead")
        if self.matcher is not None:
            breaker = getattr(self.matcher, "breaker", None)
            if breaker is not None:
                state = str(breaker.state)
                detail["matcher_breaker"] = {"state": state}
                if state != "closed":
                    degraded.append("matcher_breaker_" + state)
        if self._retained_engine is not None:
            state = str(self._retained_engine.breaker.state)
            detail["retained_breaker"] = {"state": state}
            if state != "closed":
                # retained matching degrades to the host walk — serve on
                degraded.append("retained_breaker_" + state)
        c = self._cluster
        if c is not None:
            from .cluster import PEER_PARTITIONED

            ch: dict = {"worker": c.worker_id, "peers": c.peer_count}
            partitioned = sorted(
                p
                for p, ph in c._health.items()
                if ph.state == PEER_PARTITIONED
            )
            if partitioned:
                ch["partitioned_peers"] = partitioned
                degraded.append("cluster_partitioned_peers")
            if c.topo is not None:
                neighbors = c.topo.neighbors()
                links = sum(1 for p in neighbors if p in c._writers)
                ch["epoch"] = c.topo.epoch_num()
                ch["tree_links"] = links
                ch["tree_neighbors"] = len(neighbors)
                ch["is_root"] = c.topo.is_root()
                if links < len(neighbors):
                    degraded.append("cluster_tree_edges_down")
            detail["cluster"] = ch
        if self.slo is not None:
            breached = sorted(
                name
                for name, st in self.slo.state().items()
                if st.get("breached")
            )
            detail["slo"] = {"objectives": len(self.slo.objectives)}
            if breached:
                detail["slo"]["breached"] = breached
                degraded.append("slo_breached")
        plane = self.device_stats
        if plane is not None:
            # device plane (ISSUE 18): HBM past the watermark or a
            # breached skew objective DEGRADE — the broker still
            # serves, but the multi-chip frontier is unhealthy and the
            # body says which chip-level instrument tripped. Readiness
            # NEVER flips on device telemetry.
            ratio = plane.hbm_ratio()
            detail["devices"] = {
                "hbm_ratio": round(ratio, 4),
                "hbm_watermark": plane.hbm_watermark,
                "skew_ratio": round(plane.skew_ratio(), 4),
            }
            if ratio >= plane.hbm_watermark and ratio > 0.0:
                degraded.append("hbm_watermark")
            if self.slo is not None and any(
                st.get("breached")
                and st.get("family") == "mqtt_tpu_device_skew_ratio"
                for st in self.slo.state().values()
            ):
                degraded.append("device_skew")
        ok = not not_ready
        detail["ok"] = ok
        detail["not_ready"] = not_ready
        detail["degraded"] = degraded
        return ok, detail

    def _overload_transition(self, old: str, new: str) -> None:
        """Governor transition observer: entering SHED dumps the flight
        recorder — the storm arrives with a stage-level trace attached."""
        from .overload import SHED

        if new == SHED:
            extra = {"from": old, "to": new}
            try:
                extra["gauges"] = self.overload.gauges()
            except Exception:  # pragma: no cover  # brokerlint: ok=R4 best-effort dump context; the flight dump itself still fires
                pass
            self.telemetry.trigger_dump("overload_shed", extra)

    # -- overload control plane (mqtt_tpu.overload) ------------------------

    def _outbound_pressure(self) -> float:
        """Aggregate outbound backlog — publishes parked in every
        registered client's bounded outbound queue, the running total
        the queues keep (``SessionsDue.outbound``) — normalized against
        the configured cap (the governor's 'subscribers are not
        draining' signal)."""
        total = self._due.outbound
        self._outbound_backlog = total
        return total / self.options.overload_max_outbound_backlog

    def sweep_overload(self) -> None:
        """One governor housekeeping pass (event-loop tick, 1 Hz): force
        a pressure evaluation, then evict slow consumers while shedding —
        DISCONNECT 0x97 Quota Exceeded, the reference's drop-on-slow-
        consumer posture escalated to eviction so their backlog frees.

        A slow consumer shows up two ways: its bounded outbound queue
        stays full (drops accumulate — ``outbound_full_since`` from the
        drop paths), or its TRANSPORT write buffer stays past the
        configured watermark (asyncio buffers unsent bytes unboundedly,
        which is the actual OOM vector a non-reading subscriber
        creates). Either condition persisting past the grace window
        while SHED evicts the client."""
        ov = self.overload
        if ov is None:
            return
        ov.evaluate(force=True)
        # under the shard fabric each shard sweeps ITS clients on its
        # own loop (mqtt_tpu.shards LoopShard._tick) — transport-buffer
        # reads and eviction disconnects stay loop-local, exactly the
        # single-loop sweep's invariant; the main tick covers clients
        # the main loop owns (and loop-less ones: tests, mocks)
        try:
            here: Optional[asyncio.AbstractEventLoop] = (
                asyncio.get_running_loop()
            )
        except RuntimeError:
            here = self._main_loop
        self.sweep_clients_for_loop(here, include_unowned=True)

    def sweep_clients_for_loop(
        self,
        loop: Optional[asyncio.AbstractEventLoop],
        include_unowned: bool = False,
    ) -> int:
        """One slow-consumer eviction pass over the clients ``loop``
        owns (every client when no fabric is attached — the single-loop
        path unchanged) that may hold a backlog (``SessionsDue.backlog``:
        a transport write left bytes buffered, or the outbound queue
        filled). A client whose transport holds nothing and whose queue
        is not full leaves the set: every sweep until its next such
        write or fill would find it so, record that and reset its clock,
        which is what leaving it does. Returns the evictions performed;
        the shard housekeeping tick feeds it into the per-shard
        counter."""
        ov = self.overload
        if ov is None:
            return 0
        due = self._due
        backlog = due.backlog
        buf_limit = self.options.overload_client_buffer_limit_bytes
        now = time.monotonic()
        evicted = 0
        held = tuple(backlog)
        self._ops.housekeep_visits += len(held)
        for cl in held:
            if cl.state.due is not due or cl.net.inline or cl.closed:
                # out of the registry, or never to write again: no sweep
                # looks at it (a restored session joins afresh)
                backlog.discard(cl)
                continue
            if self._fabric is not None:
                owner = cl.net.loop
                if owner is not loop and not (
                    owner is None and include_unowned
                ):
                    continue
            buffered = 0
            if cl.net.writer is not None:
                try:
                    buffered = cl.net.writer.transport.get_write_buffer_size()
                except Exception:
                    buffered = 0
            qfull = cl.state.outbound.full()
            # a consumer whose buffer SHRANK since the last sweep is
            # draining — behind, but alive; only a backlog that never
            # recedes marks a stalled consumer
            draining = buffered < cl.state.sweep_buffered
            cl.state.sweep_buffered = buffered
            if draining or not (buffered > buf_limit or qfull):
                cl.state.backlog_over_since = None
                if not buffered and not qfull:
                    backlog.discard(cl)
                    if cl.state.outbound.full():  # filled meanwhile, off the loop
                        backlog.add(cl)
                    continue
            elif cl.state.backlog_over_since is None:
                cl.state.backlog_over_since = now
            over_since = cl.state.backlog_over_since
            # the drop clock may predate this sweep's first observation
            full_since = cl.state.outbound_full_since
            if qfull and not draining and full_since is not None:
                over_since = (
                    full_since
                    if over_since is None
                    else min(over_since, full_since)
                )
            if over_since is not None and ov.evict_due(over_since):
                ov.note_eviction()
                evicted += 1
                self.log.warning(
                    "evicting slow consumer under overload: client=%s "
                    "backlogged_for=%.1fs buffered=%dB queue_full=%s",
                    cl.id,
                    now - over_since,
                    buffered,
                    qfull,
                )
                try:
                    self.disconnect_client(cl, ERR_QUOTA_EXCEEDED)
                except Code:
                    pass
                # deliberately a GRACEFUL close: a victim that resumes
                # reading still sees its queued publishes + the 0x97
                # DISCONNECT (the contract test_overload pins); one that
                # never reads leaves an unflushable transport, which the
                # BOUNDED close_all drain (listeners.Listeners) reaps at
                # shutdown instead of wedging on it
        return evicted

    def shard_backlog(self, loop: Any) -> int:
        """Queued outbound publishes across the clients one shard loop
        owns (the per-shard face of the aggregate backlog gauge). One
        scrape calls this once PER SHARD, so the walk over the client
        registry is computed once and memoized briefly — N shard gauges
        cost one pass, not N (the memo staleness is far below the
        scrape interval)."""
        now = time.monotonic()
        cached = self._shard_backlog_memo
        if cached is None or now - cached[0] > 0.5:
            totals: dict = {}
            for cl in self.clients.get_all().values():
                if not cl.closed:
                    owner = cl.net.loop
                    totals[owner] = (
                        totals.get(owner, 0) + cl.state.outbound.qsize()
                    )
            cached = (now, totals)
            self._shard_backlog_memo = cached
        return cached[1].get(loop, 0)

    def _resolve_tenant(self, cl: Client) -> None:
        """CONNECT-time tenant resolution (mqtt_tpu.tenancy): map the
        client (username first, then client id) to its tenant, scope the
        registry identity into the tenant namespace — two tenants' equal
        client ids can never collide or take each other's sessions over
        — and apply the tenant's quota class through the governor's
        priority-class machinery. Runs AFTER authentication (an
        unauthenticated client must not resolve into a tenant) and
        BEFORE _assign_priority_class (a per-user class mapping
        overrides the tenant-wide one)."""
        plane = self._tenancy
        if plane is None or cl.net.inline:
            return
        from .tenancy import scope_client_id

        username = cl.properties.username
        uname = (
            username.decode("utf-8", "replace")
            if isinstance(username, (bytes, bytearray))
            else (username or "")
        )
        tenant = plane.resolve(uname, cl.id)
        if tenant is None:
            return
        cl.tenant = tenant
        cl.id = scope_client_id(tenant.name, cl.id)
        if tenant.quota_class:
            weights = self.options.overload_priority_classes or {}
            cl.priority_class = tenant.quota_class
            cl.priority_weight = float(weights.get(tenant.quota_class, 1.0))

    def _assign_priority_class(self, cl: Client) -> None:
        """Resolve the client's shed-priority class at CONNECT
        (mqtt_tpu.overload priority-weighted shedding): the config map
        keys on username first, then client id; the resolved class's
        quota multiplier is cached on the client so the admit/read-delay
        hot paths pay one attribute read. Embedders may overwrite
        ``cl.priority_weight`` from an on_connect hook."""
        users = self.options.overload_priority_users
        if not users:
            return
        username = cl.properties.username
        uname = (
            username.decode("utf-8", "replace")
            if isinstance(username, (bytes, bytearray))
            else username
        )
        cid = cl.id
        if cid[:1] == NS_CHAR:
            # tenant clients are registered under their SCOPED id
            # (_resolve_tenant); the operator's map keys on the id the
            # client actually sent
            from .tenancy import local_client_id

            cid = local_client_id(cid)
        klass = users.get(uname) or users.get(cid)
        if klass is None:
            return
        cl.priority_class = klass
        weights = self.options.overload_priority_classes or {}
        cl.priority_weight = float(weights.get(klass, 1.0))

    def _connect_admission(self, cl: Client, listener: str) -> Optional[Code]:
        """The per-listener CONNECT admission verdict: None admits; a
        Code refuses (the caller CONNACKs it and drops the connection).
        Local/inline attachments and listeners configured with
        ``admission=False`` are exempt; admin-ACL clients (read access
        to the $SYS tree) draw from the governor's always-admit
        reserve."""
        ov = self.overload
        if (
            ov is None
            or not self.options.overload_admission
            or cl.net.inline
            or listener == LOCAL_LISTENER
        ):
            return None
        lst = self.listeners.get(listener)
        if lst is not None and not getattr(lst.config, "admission", True):
            return None
        if self._draining:
            ov.note_connect_refused()  # the gauge counts 0x89s too
            return ERR_SERVER_BUSY  # 0x89: drain, not quota
        # the ACL walk runs LAZILY inside admit_connect: only when the
        # governor would otherwise refuse and reserve budget remains —
        # the steady-state NORMAL CONNECT never pays it
        if ov.admit_connect(
            admin=lambda: self.hooks.on_acl_check(
                cl, SYS_PREFIX + "/broker/overload/state", False
            )
        ):
            return None
        return ERR_QUOTA_EXCEEDED  # 0x97

    async def establish_connection(self, listener: str, reader, writer) -> None:
        """Attach a newly accepted connection (server.go:398-401)."""
        from .shards import SHARD_TASK_ATTR

        task = asyncio.current_task()
        if task is not None and getattr(task, SHARD_TASK_ATTR, None) is None:
            # ClientsWg analog (listeners.go:43). Shard-fabric tasks are
            # tracked by their OWN shard (mqtt_tpu.shards) — the main
            # loop must never gather a foreign loop's tasks
            self.listeners.client_tasks.add(task)
            task.add_done_callback(self.listeners.client_tasks.discard)
        cl = self.new_client(reader, writer, listener, "", False)
        await self.attach_client(cl, listener)

    async def attach_client(self, cl: Client, listener: str) -> None:
        """Validate an incoming connection, run the CONNECT handshake, and
        read packets until disconnect (server.go:405-494)."""
        # the loop OWNING this transport: every cross-shard write/close
        # marshals onto it (mqtt_tpu.shards); single-loop brokers record
        # the main loop and every check short-circuits loop-local
        cl.net.loop = asyncio.get_running_loop()
        cl._handler_task = asyncio.current_task()
        if self._fabric is not None:
            # per-shard read-side decode batching, default-on inside
            # the fabric (ISSUE 15)
            cl.scan_gate = self._fabric.gate_for(cl.net.loop)
        cl.start_write_loop()
        err: Optional[Exception] = None
        connected = False
        try:
            pk = await self.read_connection_packet(cl)
            cl.parse_connect(listener, pk)
            if self.info.clients_connected >= self.options.capabilities.maximum_clients:
                if cl.properties.protocol_version < 5:
                    self.send_connack(cl, ERR_SERVER_UNAVAILABLE, False, None)
                else:
                    self.send_connack(cl, ERR_SERVER_BUSY, False, None)
                raise ERR_SERVER_BUSY()

            code = self.validate_connect(cl, pk)  # [MQTT-3.1.4-1] [MQTT-3.1.4-2]
            if code != CODE_SUCCESS:
                self.send_connack(cl, code, False, None)
                raise code()  # [MQTT-3.2.2-7] [MQTT-3.1.4-6]

            self.hooks.on_connect(cl, pk)  # error aborts

            cl.refresh_deadline(cl.state.keepalive)
            if not self.hooks.on_connect_authenticate(cl, pk):  # [MQTT-3.1.4-2]
                self.send_connack(cl, ERR_BAD_USERNAME_OR_PASSWORD, False, None)
                raise ERR_BAD_USERNAME_OR_PASSWORD()

            self._resolve_tenant(cl)
            self._assign_priority_class(cl)
            # per-listener admission (mqtt_tpu.overload federation): a
            # broker in THROTTLE/SHED refuses NEW connections up front —
            # CONNACK 0x97 Quota Exceeded (0x89 while draining) — except
            # the small always-admit reserve for admin-ACL clients.
            # AFTER authentication, deliberately: an unauthenticated
            # client claiming the admin identity must not be able to
            # burn the operator's reserve slots
            refusal = self._connect_admission(cl, listener)
            if refusal is not None:
                if cl.properties.protocol_version < 5:
                    # v3 CONNACK codes stop at 5: 0x97/0x89 have no
                    # translation, so the v3 wire answer is the same
                    # one the maximum_clients refusal uses
                    self.send_connack(cl, ERR_SERVER_UNAVAILABLE, False, None)
                else:
                    self.send_connack(cl, refusal, False, None)
                raise refusal()

            with self._conn_lock:
                self.info.clients_connected += 1
            connected = True
            if cl.tenant is not None and self._tenancy is not None:
                self._tenancy.note_connect(cl.tenant)

            self.hooks.on_session_establish(cl, pk)

            # cross-shard takeover quiesce (mqtt_tpu.shards): the
            # session migration below clones/clears the EXISTING
            # client's inflight + subscriptions, which is only safe
            # once its owner loop has stopped serving it — disconnect
            # it ON that loop and AWAIT completion before touching its
            # state (the single-loop path needs none of this: the
            # migration and the old client share one loop)
            if self._fabric is not None:
                existing = self.clients.get(cl.id)
                if existing is not None and not self._client_loop_local(
                    existing
                ):
                    await self._quiesce_takeover(existing)

            session_present = self.inherit_client_session(pk, cl)
            self.clients.add_client(cl)  # [MQTT-4.1.0-1]

            self.send_connack(cl, code, session_present, None)  # [MQTT-3.1.4-5]
            self.will_delayed.delete(cl.id)  # [MQTT-3.1.3-9]

            if session_present:
                cl.resend_inflight_messages(True)

            self.hooks.on_session_established(cl, pk)

            try:
                await cl.read(self.receive_packet)
            except Exception as e:
                err = e
                self.send_lwt(cl)
                cl.stop(e)
            else:
                cl.properties.will = Will()  # [MQTT-3.14.4-3] [MQTT-3.1.2-10]

            self.log.debug(
                "client disconnected: error=%s client=%s remote=%s listener=%s",
                err, cl.id, cl.net.remote, listener,
            )

            expire = (
                cl.properties.protocol_version == 5
                and cl.properties.props.session_expiry_interval == 0
            ) or (cl.properties.protocol_version < 5 and cl.properties.clean)
            self.hooks.on_disconnect(cl, err, expire)

            if expire and not cl.is_taken_over:
                cl.clear_inflights()
                self.unsubscribe_client(cl)
                self.clients.delete(cl.id)  # [MQTT-4.1.0-2]
        except Exception as e:
            err = e
        finally:
            if connected:
                with self._conn_lock:
                    self.info.clients_connected -= 1
                if cl.tenant is not None and self._tenancy is not None:
                    self._tenancy.note_disconnect(cl.tenant)
            cl.stop(err)
        if err is not None and not isinstance(
            err, (asyncio.IncompleteReadError, ConnectionError, ConnectionClosedError)
        ):
            self.log.debug("connection ended: %s", err)

    async def read_connection_packet(self, cl: Client) -> Packet:
        """The first packet MUST be CONNECT [MQTT-3.1.0-1]
        (server.go:498-515)."""
        fh = FixedHeader()
        await cl.read_fixed_header(fh)
        if fh.type != pkts.CONNECT:
            raise ERR_PROTOCOL_VIOLATION_REQUIRE_FIRST_CONNECT()
        return await cl.read_packet(fh)

    def receive_packet(self, cl: Client, pk: Packet) -> None:
        """Process one inbound packet; a v5 error code disconnects the client
        (server.go:519-534). Synchronous: a PUBLISH that went to the
        publish staging loop is parked when this returns, and its fan-out,
        ``on_published`` and ``on_packet_processed`` run in its batch's
        completion (``_complete_staged``). The caller's read loop waits
        once a socket read for its connection's parked publishes, so the
        publishing client blocks on its own fan-out (the reference's
        per-connection-goroutine semantics) while other clients proceed."""
        try:
            self.process_packet(cl, pk)
        except Code as code:
            self._packet_error(cl, code)
            raise

    def _packet_error(self, cl: Client, code: Code) -> None:
        if cl.properties.protocol_version == 5 and code.code >= ERR_UNSPECIFIED_ERROR.code:
            try:
                self.disconnect_client(cl, code)
            except Exception:  # brokerlint: ok=R4 already on the error path; the warning below records the packet error
                pass
        self.log.warning(
            "error processing packet: error=%s client=%s listener=%s",
            code, cl.id, cl.net.listener,
        )

    def validate_connect(self, cl: Client, pk: Packet) -> Code:
        """Connect compliance checks beyond the codec's (server.go:537-556)."""
        code = pk.connect_validate()
        if code != CODE_SUCCESS:
            return code
        if (
            cl.properties.protocol_version < 5
            and not pk.connect.clean
            and pk.connect.client_identifier == ""
        ):
            return ERR_UNSPECIFIED_ERROR
        caps = self.options.capabilities
        if cl.properties.protocol_version < caps.minimum_protocol_version:
            return ERR_UNSUPPORTED_PROTOCOL_VERSION  # [MQTT-3.1.2-2]
        if cl.properties.will.qos > caps.maximum_qos:
            return ERR_QOS_NOT_SUPPORTED  # [MQTT-3.2.2-12]
        if cl.properties.will.retain and caps.retain_available == 0:
            return ERR_RETAIN_NOT_SUPPORTED  # [MQTT-3.2.2-13]
        return code

    async def _quiesce_takeover(self, existing: Client) -> None:
        """Disconnect a to-be-taken-over client ON its owning shard's
        loop and wait for it: after this, the old owner's loop can no
        longer be mutating the session state the takeover migrates
        (its read loop observes ``closed`` before processing anything
        else). The drain also awaits the old ATTACH HANDLER itself, so
        its disconnect epilogue (the expire branch, registry delete)
        has fully run before the migration reads the registry — for a
        persistent session that epilogue keeps the state (not taken
        over yet, not expiring); for a clean session it discards it,
        which is what a clean takeover does anyway. A dead/stopped
        owner loop degrades to a direct stop — the client was not
        being served."""
        loop = existing.net.loop
        if loop is None or not loop.is_running():
            existing.stop(ERR_SESSION_TAKEN_OVER())
            return

        async def _disconnect_and_drain() -> None:
            try:
                self.disconnect_client(existing, ERR_SESSION_TAKEN_OVER)
            except Code:
                pass
            task = existing._handler_task
            if task is not None and task is not asyncio.current_task():
                try:
                    await asyncio.wait_for(asyncio.shield(task), timeout=4.0)
                except Exception:  # brokerlint: ok=R4 bounded drain; a wedged old handler must not hold the CONNECT hostage
                    pass

        try:
            cfut = asyncio.run_coroutine_threadsafe(
                _disconnect_and_drain(), loop
            )
        except RuntimeError:
            existing.stop(ERR_SESSION_TAKEN_OVER())
            return
        try:
            await asyncio.wait_for(asyncio.wrap_future(cfut), timeout=5.0)
        except asyncio.TimeoutError:
            # a wedged owner loop must not hold the CONNECT hostage;
            # the closed flag still fences its data plane
            existing.stop(ERR_SESSION_TAKEN_OVER())

    def inherit_client_session(self, pk: Packet, cl: Client) -> bool:
        """Session takeover: disconnect the existing client with the same id
        and inherit (or discard) its state (server.go:561-603)."""
        existing = self.clients.get(cl.id)
        if existing is not None:
            try:
                self.disconnect_client(existing, ERR_SESSION_TAKEN_OVER)  # [MQTT-3.1.4-3]
            except Code:
                pass
            if pk.connect.clean or (
                existing.properties.clean and existing.properties.protocol_version < 5
            ):  # [MQTT-3.1.2-4] [MQTT-3.1.4-4]
                self.unsubscribe_client(existing)
                existing.clear_inflights()
                existing.state.is_taken_over = True  # after unsubscribe
                return False  # [MQTT-3.2.2-3]

            existing.state.is_taken_over = True
            if len(existing.state.inflight) > 0:
                cl.state.inflight = existing.state.inflight.clone()  # [MQTT-3.1.2-5]
                if (
                    cl.state.inflight.maximum_receive_quota == 0
                    and self.options.capabilities.receive_maximum != 0
                ):
                    cl.state.inflight.reset_receive_quota(
                        self.options.capabilities.receive_maximum
                    )
                    cl.state.inflight.reset_send_quota(cl.properties.props.receive_maximum)

            for sub in existing.state.subscriptions.get_all().values():
                existed = not self.topics.subscribe(cl.id, sub)  # [MQTT-3.8.4-3]
                if not existed:
                    self.info.subscriptions += 1
                cl.state.subscriptions.add(sub.filter, sub)

            # clean existing state so sequential takeovers don't leak
            self.unsubscribe_client(existing)
            existing.clear_inflights()

            self.log.debug(
                "session taken over: client=%s old_remote=%s new_remote=%s",
                cl.id, existing.net.remote, cl.net.remote,
            )
            return True  # [MQTT-3.2.2-3]

        if self.info.clients_connected > self.info.clients_maximum:
            self.info.clients_maximum += 1
        return False  # [MQTT-3.2.2-2]

    def send_connack(
        self, cl: Client, reason: Code, present: bool, properties: Optional[Properties]
    ) -> None:
        """Issue a CONNACK, translating v5 codes for v3 clients
        (server.go:606-663)."""
        if properties is None:
            properties = Properties()
        properties.receive_maximum = self.options.capabilities.receive_maximum  # 3.2.2.3.3
        if cl.state.server_keepalive:  # set dynamically via the on_connect hook
            properties.server_keep_alive = cl.state.keepalive  # [MQTT-3.1.2-21]
            properties.server_keep_alive_flag = True

        if reason.code >= ERR_UNSPECIFIED_ERROR.code:
            if cl.properties.protocol_version < 5:
                reason = V5_CODES_TO_V3.get(reason, reason)
            properties.reason_string = reason.reason
            ack = Packet(
                fixed_header=FixedHeader(type=pkts.CONNACK),
                session_present=False,  # [MQTT-3.2.2-6]
                reason_code=reason.code,  # [MQTT-3.2.2-8]
                properties=properties,
            )
            cl.write_packet(ack)
            return

        caps = self.options.capabilities
        if caps.maximum_qos < 2:
            properties.maximum_qos = caps.maximum_qos  # [MQTT-3.2.2-9]
            properties.maximum_qos_flag = True
        if cl.properties.props.assigned_client_id:
            properties.assigned_client_id = cl.properties.props.assigned_client_id  # [MQTT-3.1.3-7]
        if cl.properties.props.session_expiry_interval > caps.maximum_session_expiry_interval:
            properties.session_expiry_interval = caps.maximum_session_expiry_interval
            properties.session_expiry_interval_flag = True
            cl.properties.props.session_expiry_interval = properties.session_expiry_interval
            cl.properties.props.session_expiry_interval_flag = True

        ack = Packet(
            fixed_header=FixedHeader(type=pkts.CONNACK),
            session_present=present,
            reason_code=reason.code,  # [MQTT-3.2.2-8]
            properties=properties,
        )
        cl.write_packet(ack)

    # -- packet processing -------------------------------------------------

    def process_packet(self, cl: Client, pk: Packet) -> None:
        """Dispatch one inbound packet by type (server.go:667-730); raises a
        Code on protocol errors. A PUBLISH parked with the staging loop
        leaves its post-processing (``on_packet_processed``, the quota
        drain) to its batch's completion, after its fan-out (hook order —
        on_published before on_packet_processed — is preserved there)."""
        t = pk.fixed_header.type
        err: Optional[Exception] = None
        parked = False
        try:
            if t == pkts.CONNECT:
                self.process_connect(cl, pk)
            elif t == pkts.DISCONNECT:
                self.process_disconnect(cl, pk)
            elif t == pkts.PINGREQ:
                self.process_pingreq(cl, pk)
            elif t == pkts.PUBLISH:
                parked = self._dispatch_publish(cl, pk)
            elif t == pkts.PUBACK:
                self.process_puback(cl, pk)
            elif t == pkts.PUBREC:
                self.process_pubrec(cl, pk)
            elif t == pkts.PUBREL:
                self.process_pubrel(cl, pk)
            elif t == pkts.PUBCOMP:
                self.process_pubcomp(cl, pk)
            elif t == pkts.SUBSCRIBE:
                code = pk.subscribe_validate()
                if code != CODE_SUCCESS:
                    raise code()
                self.process_subscribe(cl, pk)
            elif t == pkts.UNSUBSCRIBE:
                code = pk.unsubscribe_validate()
                if code != CODE_SUCCESS:
                    raise code()
                self.process_unsubscribe(cl, pk)
            elif t == pkts.AUTH:
                code = pk.auth_validate()
                if code != CODE_SUCCESS:
                    raise code()
                self.process_auth(cl, pk)
            else:
                raise pkts.ERR_NO_VALID_PACKET_AVAILABLE()
        except Exception as e:
            err = e
            raise
        finally:
            if not parked:
                self.hooks.on_packet_processed(cl, pk, err)

        if not parked:
            self._drain_quota_starved(cl)

    def _dispatch_publish(self, cl: Client, pk: Packet) -> bool:
        """Validate + process one PUBLISH; True when it was parked with
        the staging loop (``process_publish``)."""
        code = pk.publish_validate(self.options.capabilities.topic_alias_maximum)
        if code != CODE_SUCCESS:
            raise code()
        return self.process_publish(cl, pk)

    def _drain_quota_starved(self, cl: Client) -> None:
        # post-process: drain one quota-starved inflight if quota freed up
        inflight = cl.settled_inflight()  # a slice's run parks first
        if len(inflight) > 0 and inflight.send_quota > 0:
            nxt = inflight.next_immediate()
            if nxt is not None:
                try:
                    cl.write_packet(nxt)
                except Exception:  # brokerlint: ok=R4 client mid-teardown; the inflight store still reconciles below
                    pass
                if inflight.delete(nxt.packet_id):
                    self.info.inflight -= 1
                inflight.decrease_send_quota()

    def process_connect(self, cl: Client, pk: Packet) -> None:
        """A second CONNECT is a protocol violation [MQTT-3.1.0-2]
        (server.go:734-737)."""
        self.send_lwt(cl)
        raise ERR_PROTOCOL_VIOLATION_SECOND_CONNECT()

    def process_pingreq(self, cl: Client, pk: Packet) -> None:
        cl.write_packet(Packet(fixed_header=FixedHeader(type=pkts.PINGRESP)))  # [MQTT-3.12.4-1]

    # -- inline client api -------------------------------------------------

    def publish(self, topic: str, payload: bytes, retain: bool, qos: int) -> None:
        """Inline publish into the broker, bypassing ACL (server.go:752-767)."""
        if not self.options.inline_client:
            raise InlineClientNotEnabledError()
        assert self.inline_client is not None  # built in __init__ with the option on
        self.inject_packet(
            self.inline_client,
            Packet(
                fixed_header=FixedHeader(type=pkts.PUBLISH, qos=qos, retain=retain),
                topic_name=topic,
                payload=payload,
                packet_id=qos,  # unprocessed inbound qos still needs a packet id
            ),
        )

    def subscribe(self, filter: str, subscription_id: int, handler: InlineSubFn) -> None:
        """Inline (in-process) subscription (server.go:771-808)."""
        if not self.options.inline_client:
            raise InlineClientNotEnabledError()
        assert self.inline_client is not None  # built in __init__ with the option on
        if handler is None:
            raise ERR_INLINE_SUBSCRIPTION_HANDLER_INVALID()
        predicates: tuple = ()
        if self._predicates is not None:
            base, pred_suffix = split_predicate_suffix(filter)
            if pred_suffix:
                filter = base
                predicates = (pred_suffix,)
        if not is_valid_filter(filter, False):
            raise ERR_TOPIC_FILTER_INVALID()
        if self._predicates is not None:
            if predicates:
                self._predicates.register(predicates[0])
            # re-subscribing the same (identifier, filter) REPLACES the
            # stored inline subscription: drop the replaced one's rule
            # refs (after registering, like the client SUBSCRIBE path)
            replaced = self.topics.inline_subscription(subscription_id, filter)
            if replaced is not None and replaced.predicates:
                self._predicates.release(replaced.predicates)
        subscription = Subscription(
            identifier=subscription_id, filter=filter, predicates=predicates
        )
        pk = self.hooks.on_subscribe(
            self.inline_client,
            Packet(
                origin=self.inline_client.id,
                fixed_header=FixedHeader(type=pkts.SUBSCRIBE),
                filters=[subscription],
            ),
        )
        inline_sub = InlineSubscription(
            filter=filter,
            identifier=subscription_id,
            handler=handler,
            predicates=predicates,
        )
        self.topics.inline_subscribe(inline_sub)
        self.hooks.on_subscribed(self.inline_client, pk, bytes([CODE_SUCCESS.code]))
        for pkv in self.topics.messages(filter):  # [MQTT-3.8.4-4]
            if self._predicates is not None and not self._predicates.passes_retained(
                subscription, bytes(pkv.payload)
            ):
                continue
            handler(self.inline_client, subscription, pkv)

    def unsubscribe(self, filter: str, subscription_id: int) -> None:
        """Remove an inline subscription (server.go:813-836)."""
        if not self.options.inline_client:
            raise InlineClientNotEnabledError()
        assert self.inline_client is not None  # built in __init__ with the option on
        if self._predicates is not None:
            base, pred_suffix = split_predicate_suffix(filter)
            if pred_suffix:
                filter = base
        if not is_valid_filter(filter, False):
            raise ERR_TOPIC_FILTER_INVALID()
        pk = self.hooks.on_unsubscribe(
            self.inline_client,
            Packet(
                origin=self.inline_client.id,
                fixed_header=FixedHeader(type=pkts.UNSUBSCRIBE),
                filters=[Subscription(identifier=subscription_id, filter=filter)],
            ),
        )
        if self._predicates is not None:
            # release the STORED subscription's rule refs, and only when
            # a subscription is actually removed — an unsubscribe for a
            # (filter, id) that never existed must not underflow a rule
            # other live subscriptions still reference
            stored = self.topics.inline_subscription(subscription_id, filter)
            removed = self.topics.inline_unsubscribe(subscription_id, filter)
            if removed and stored is not None and stored.predicates:
                self._predicates.release(stored.predicates)
        else:
            self.topics.inline_unsubscribe(subscription_id, filter)
        self.hooks.on_unsubscribed(self.inline_client, pk)

    def inject_packet(self, cl: Client, pk: Packet) -> None:
        """Process a packet as if sent by ``cl``, bypassing the network
        (server.go:840-854). A staged PUBLISH is parked when this returns
        and fans out with its batch, like one read from a socket."""
        pk.protocol_version = cl.properties.protocol_version
        self.process_packet(cl, pk)
        self.info.packets_received += 1
        if pk.fixed_header.type == pkts.PUBLISH:
            self.info.messages_received += 1

    # -- publish flow ------------------------------------------------------

    def process_publish(self, cl: Client, pk: Packet) -> bool:
        """The publish hot path (server.go:857-968). With the staging loop
        active the publish is parked with it and True is returned: its
        fan-out and everything after it run in its batch's completion
        (``_complete_staged``); QoS acks are already written. False: the
        publish was dealt with here (fanned out, refused or dropped)."""
        if not cl.net.inline and not is_valid_filter(pk.topic_name, True):
            return False

        if cl.state.inflight.receive_quota == 0:
            self.disconnect_client(cl, ERR_RECEIVE_MAXIMUM)  # ~[MQTT-3.3.4-7/-8]
            return False

        if not cl.net.inline and not self.hooks.on_acl_check(cl, pk.topic_name, True):
            self._deny_publish(cl, pk)
            return False

        pk.origin = cl.id
        pk.created = int(time.time())  # brokerlint: ok=R3 packet creation stamp is wall-clock (persists/expires across restarts)
        expiry = _minimum(
            self.options.capabilities.maximum_message_expiry_interval,
            pk.properties.message_expiry_interval,
        )
        if expiry > 0:
            pk.expiry = pk.created + expiry

        if not cl.net.inline:
            pki = cl.state.inflight.get(pk.packet_id)
            if pki is not None:
                if pki.fixed_header.type == pkts.PUBREC:  # [MQTT-4.3.3-10]
                    ack = self.build_ack(
                        pk.packet_id, pkts.PUBREC, 0, pk.properties, ERR_PACKET_IDENTIFIER_IN_USE
                    )
                    cl.write_packet(ack)
                    return False
                if cl.state.inflight.delete(pk.packet_id):  # [MQTT-4.3.2-5]
                    self.info.inflight -= 1

        if pk.properties.topic_alias_flag and pk.properties.topic_alias > 0:  # [MQTT-3.3.2-11]
            pk.topic_name = cl.state.topic_aliases.inbound.set(
                pk.properties.topic_alias, pk.topic_name
            )

        if pk.fixed_header.qos > self.options.capabilities.maximum_qos:
            pk.fixed_header.qos = self.options.capabilities.maximum_qos  # [MQTT-3.2.2-9]

        # overload admission (mqtt_tpu.overload): while SHEDDING, traffic
        # past the per-client window budget is refused GRACEFULLY — QoS0
        # drops (counted), QoS1/2 acks 0x97 Quota Exceeded (v5; v3/v4
        # acks carry no reason code, so the excess is simply not fanned
        # out — the reference's drop-on-overload posture). Runs after
        # alias resolution so alias state stays coherent across sheds,
        # and never touches $SYS/LWT/retained housekeeping (those flow
        # through publish_to_subscribers, not here).
        if (
            not cl.net.inline
            and self.overload is not None
            and not self.overload.admit(cl)
        ):
            self._shed_publish(cl, pk)
            return False

        # telemetry stage clock (attached by the read loop on sampled
        # publishes): everything from decode's end to here — validation,
        # quota, alias resolution, the overload admission verdict
        clock = getattr(pk, "_tclock", None)
        tele = self.telemetry
        if (
            tele is not None
            and tele.tracer is not None
            and pk.properties.user
        ):
            # an inbound v5 `trace-id` user property adopts the client's
            # trace id (mqtt_tpu.tracing); off the adopted path this is
            # one empty-list check
            clock = tele.adopt_trace(pk)
        if clock is not None:
            self._stamp_admission(pk, clock)

        try:
            pk = self.hooks.on_publish(cl, pk)
        except Code as e:
            if e == ERR_REJECT_PACKET:
                return False
            if e == CODE_SUCCESS_IGNORE:
                pk.ignore = True
            elif cl.properties.protocol_version == 5 and pk.fixed_header.qos > 0:
                cl.write_packet(self.build_ack(pk.packet_id, pkts.PUBACK, 0, pk.properties, e))
                return False
            # other errors: continue with the original packet (reference
            # server.go:912-925 falls through)

        if cl.tenant is not None:
            # tenant namespace (mqtt_tpu.tenancy): validation, the ACL,
            # aliases, admission, and the on_publish hook all saw the
            # LOCAL topic above; matching, retention, staging, and
            # cluster forwarding operate on the scoped key from here
            # (deliveries strip it back off at the fan-out choke point)
            pk.topic_name = ns_scope_topic(cl.tenant.name, pk.topic_name)
            cl.tenant.messages_in += 1
            cl.tenant.bytes_in += len(pk.payload)

        if pk.fixed_header.retain and self._retained_quota_refused(cl, pk):
            # tenant retained COUNT cap (ISSUE 16): refuse the whole
            # publish — accepting the fan-out while silently dropping
            # retention would leave the publisher believing the topic is
            # retained. Same graceful posture as overload: QoS0 drops
            # (counted), QoS1/2 ack 0x97 Quota Exceeded.
            self._shed_publish(cl, pk)
            return False

        if pk.fixed_header.retain:  # [MQTT-3.3.1-5]
            self.retain_message(cl, pk)

        # inline clients can't handle PUBREC/PUBREL: treat as qos 0 inbound
        if pk.fixed_header.qos == 0 or cl.net.inline:
            if self._stage is not None and not cl.net.inline:
                return self._park_publish(cl, pk)
            self.publish_to_subscribers(pk)
            self._finish_publish_clock(pk)
            self.hooks.on_published(cl, pk)
            return False

        cl.state.inflight.decrease_receive_quota()
        ack = self.build_ack(
            pk.packet_id, pkts.PUBACK, 0, pk.properties, QOS_CODES[pk.fixed_header.qos]
        )  # [MQTT-4.3.2-4]
        if pk.fixed_header.qos == 2:
            ack = self.build_ack(
                pk.packet_id, pkts.PUBREC, 0, pk.properties, CODE_SUCCESS
            )  # [MQTT-3.3.4-1] [MQTT-4.3.3-8]

        if cl.state.inflight.set(ack):
            self.info.inflight += 1
            self.hooks.on_qos_publish(cl, ack, ack.created, 0)

        cl.write_packet(ack)

        if pk.fixed_header.qos == 1:
            if cl.state.inflight.delete(ack.packet_id):
                self.info.inflight -= 1
            cl.state.inflight.increase_receive_quota()
            self.hooks.on_qos_complete(cl, ack)

        if self._stage is not None and not cl.net.inline:
            return self._park_publish(cl, pk)
        self.publish_to_subscribers(pk)
        self._finish_publish_clock(pk)
        self.hooks.on_published(cl, pk)
        return False

    def _deny_publish(self, cl: Client, pk: Packet) -> None:
        """The write ACL refused this publish: QoS0 is dropped in
        silence; a v3/v4 client is disconnected (the raise is
        ``disconnect_client``'s); a v5 client gets its ack with 0x87."""
        if pk.fixed_header.qos == 0:
            return
        if cl.properties.protocol_version != 5:
            self.disconnect_client(cl, ERR_NOT_AUTHORIZED)
            return
        ack_type = pkts.PUBREC if pk.fixed_header.qos == 2 else pkts.PUBACK
        cl.write_packet(
            self.build_ack(pk.packet_id, ack_type, 0, pk.properties, ERR_NOT_AUTHORIZED)
        )

    def _shed_publish(self, cl: Client, pk: Packet) -> None:
        """Refuse one publish gracefully, counted (the overload
        governor's shed, a tenant's retained cap): QoS0 drops, QoS1/2
        ack 0x97 Quota Exceeded (a v3/v4 ack carries no reason code: the
        publish is simply not fanned out)."""
        self.info.messages_dropped += 1
        if cl.tenant is not None:
            # per-tenant shed accounting: quota classes must be
            # visibly shaping who sheds (mqtt_tpu.tenancy)
            cl.tenant.messages_dropped += 1
        if pk.fixed_header.qos == 0:
            return
        ack_type = pkts.PUBREC if pk.fixed_header.qos == 2 else pkts.PUBACK
        cl.write_packet(
            self.build_ack(
                pk.packet_id, ack_type, 0, pk.properties, ERR_QUOTA_EXCEEDED
            )
        )

    def _stamp_admission(self, pk: Packet, clock) -> None:
        """A sampled publish is past admission: the clock's stamp and
        what rides the sampling verdict with it."""
        clock.stamp("admission")
        trace_id = getattr(clock, "trace_id", None)
        if trace_id is not None and self.options.trace_user_property:
            # client-visible traces: subscribers (and peers on the
            # packet leg) see the trace id as a v5 user property
            from .telemetry import TRACE_USER_PROPERTY

            if not any(
                u.key == TRACE_USER_PROPERTY for u in pk.properties.user
            ):
                pk.properties.user.append(
                    UserProperty(TRACE_USER_PROPERTY, trace_id)
                )

    def _finish_publish_clock(self, pk: Packet) -> None:
        """Close out a sampled publish's stage clock after fan-out: the
        final stamp is the fanout write leg, then the record lands in
        the per-stage histograms + flight-recorder ring — and the
        arrival->flush total lands in the per-tenant delivery-latency
        SLI (path=local), the number the SLO engine burns against
        (ISSUE 14)."""
        clock = getattr(pk, "_tclock", None)
        if clock is not None:
            setattr(pk, "_tclock", None)  # a clock observes exactly once
            if not any(s in ("encode", "flush") for s, _ in clock.stages):
                # the batched path already split the fan-out leg into
                # encode/flush sub-stamps; telemetry synthesizes the
                # coarse ``fanout`` stage from their sum (continuity
                # with pre-split rounds — exp/stage_gate.py)
                clock.stamp("fanout")
            self.telemetry.observe_publish(
                clock, pk.topic_name, pk.fixed_header.qos
            )
            self._observe_delivery_sli(clock, pk, "local")

    def _observe_delivery_sli(self, clock, pk: Packet, path: str) -> None:
        """Fold one finished clock into the delivery-latency SLI: the
        tenant label comes off the scoped topic, the value is the
        clock's decode->flush total plus (remote path) the origin
        worker's elapsed stamp."""
        tele = self.telemetry
        if tele is None or not tele.delivery_sli:
            return
        topic = pk.topic_name
        tenant = ns_tenant(topic) if topic[:1] == NS_CHAR else ""
        tele.observe_delivery(
            clock.total() + getattr(clock, "remote_base", 0.0),
            tenant,
            pk.fixed_header.qos,
            path,
            trace_id=getattr(clock, "trace_id", None),
        )

    def _finish_remote_clock(self, pk: Packet) -> None:
        """Close a mesh-forwarded publish's receiving-side clock
        (telemetry.RemoteStageClock, attached by cluster delivery): the
        remote-path delivery SLI reads origin-elapsed + local segment.
        Never routed through observe_publish — remote deliveries must
        not skew this worker's pipeline-stage histograms or flight
        ring."""
        clock = getattr(pk, "_tclock", None)
        if clock is None:
            return
        setattr(pk, "_tclock", None)
        if not any(s in ("encode", "flush") for s, _ in clock.stages):
            clock.stamp("fanout")
        self._observe_delivery_sli(clock, pk, "remote")

    def _park_publish(self, cl: Client, pk: Packet) -> bool:
        """Park one publish with the staging loop: the device match batch
        resolves off the event loop and the publish fans out in its
        batch's completion, ``_complete_staged`` (SURVEY.md §7 stage 4;
        seam: server.go:984-1021). No task, future or coroutine is made
        for it. True: parked. A publish the stage does not admit is
        walked on the host inside the call and still completes in its
        place behind this connection's earlier publishes; only when the
        connection has none in the stage has it completed already."""
        if pk.ignore:
            self.hooks.on_published(cl, pk)
            return False
        self._stamp_publish_expiry(pk)
        # MQTT+ predicate plane: extract the payload features ONCE
        # on the host; the stage batches them to the device beside
        # the tokenized topics and stamps the resolved pass bits
        # back onto this carrier (mqtt_tpu.predicates)
        eng = self._predicates
        entry = Parked(
            self._staged_completion,
            getattr(pk, "_tclock", None),
            eng.features_for(bytes(pk.payload))
            if eng is not None and eng.active
            else None,
            # encrypted-namespace publishes carry a decrypt job whose
            # keystream dispatch rides the same staged batch
            # (mqtt_tpu.tenancy.RecryptJob through MatchStage)
            self._recrypt_job_for(cl, pk),
            cl,
            pk,
        )
        try:
            here = entry.loop = asyncio.get_running_loop()
        except RuntimeError:
            here = None  # no loop on this thread: the stage's completes it
        if here is not None and here is cl.net.loop:
            # parked from the connection's own loop: its read loop does
            # not read on before this publish has fanned out. With
            # nothing of this connection in the stage a fallback can
            # overtake nothing ([MQTT-4.6.0-5]) and may complete at once.
            entry.counted = True
            entry.alone = not cl._staged
            cl._staged += 1
        self._stage.park(pk.topic_name, entry)
        return True

    def ingest_run(
        self, cl: Client, rbuf: bytearray, frames: list, i: int, start: int
    ) -> int:
        """Take in a run of one scan's PUBLISH frames in one call: from
        ``frames[i]`` on, every frame in turn that is a v3.1.1 QoS0 or
        QoS1 PUBLISH without RETAIN (``clients.RUN_FIRST_BYTES``) and
        that nothing below refuses, each decoded straight from ``rbuf``,
        checked, acknowledged and parked as ``process_publish`` would
        have it, the whole run parked at once (``MatchStage.park_many``).
        ``start`` is where ``frames[i]`` begins. Returns how many frames
        it took (their counters advanced as the per-frame path advances
        them: ``info``, the connection's publish count, the 1-in-N clock
        draw), or -1 when the run's gate is shut for this connection.

        The gate, on what the code can see and nothing else: a staging
        loop to park with; no hook that takes the packet as read, the
        publish before it is taken, the inflight bookkeeping around an
        ack or the ack as a packet (``_INGEST_RUN_EVENTS``, cached per
        hooks generation); a network client speaking v3.1.1 outside any
        tenant (so no namespace, no re-encryption) and no live payload
        predicates. The frame that ends a run is not touched: the caller
        hands it to the per-frame path, the one owner of every error,
        drop and edge case (a malformed or empty topic, a wildcard or
        ``$``-topic, bad UTF-8, a packet id of 0 or one in the inflight
        map, receive-maximum exhausted), and the next eligible frame
        opens a new run.

        What a taken frame skips is what the gate proved nobody can see:
        the ``Packet``'s unused members (``Packet.inbound_publish``), the
        inflight lookup of QoS0's id 0 (never stored), and around a QoS1
        ack the ``inflight.set`` / ``delete`` and quota down-and-up that
        net to nothing; the ack leaves as its four bytes
        (``Client.write_puback``). The write ACL and the overload
        governor are asked once a publish, with ``process_publish``'s
        outcomes for a refusal (``_deny_publish``, ``_shed_publish``)."""
        stage = self._stage
        eng = self._predicates
        if (
            stage is None
            or cl.net.inline
            or cl.tenant is not None
            or cl.properties.protocol_version != 4
            or (eng is not None and eng.active)
            or not self._no_hook_provides("ingest_run", _INGEST_RUN_EVENTS)
        ):
            return -1
        caps = self.options.capabilities
        qos1_ok = caps.maximum_qos >= 1
        state = cl.state
        inflight = state.inflight
        acl = self.hooks.on_acl_check
        overload = self.overload
        tele = self.telemetry
        complete = self._staged_completion
        origin = cl.id
        created = int(time.time())  # brokerlint: ok=R3 packet creation stamp is wall-clock (persists/expires across restarts)
        # a v3.1.1 publish has no expiry interval of its own
        expiry = caps.maximum_message_expiry_interval
        expiry = created + expiry if expiry > 0 else 0
        try:
            here = asyncio.get_running_loop()
        except RuntimeError:
            here = None  # no loop on this thread: the stage's completes them
        # parked from the connection's own loop: its read loop does not
        # read on before they have fanned out (_park_publish)
        counted = here is not None and here is cl.net.loop
        n = len(frames)
        # the clock draws of the frames ahead that are sure to be None
        # are added once, at the run's end; ``sampled`` is the taken
        # frame whose draw is made for real
        sampled = n if tele is None else tele.quiet_draws(n - i)
        drawn = 0
        items: list = []
        taken = 0
        k = i
        try:
            while k < n:
                f = frames[k]
                fb = f.first_byte
                if fb not in RUN_FIRST_BYTES:
                    break
                due = taken == sampled
                if due:
                    t_frame = time.perf_counter()
                off = f.body_offset
                end = off + f.remaining
                qos = (fb >> 1) & 1
                if f.remaining < 2:
                    break
                t0 = off + 2
                t1 = t0 + ((rbuf[off] << 8) | rbuf[off + 1])
                if t1 == t0 or t1 + 2 * qos > end:
                    break  # no topic, or the frame ends inside it
                try:
                    topic = rbuf[t0:t1].decode("utf-8")
                except UnicodeDecodeError:
                    break
                if (
                    topic[0] == "$"
                    or "+" in topic
                    or "#" in topic
                    or "\x00" in topic
                ):
                    break  # publish_validate's and is_valid_filter's
                pid = 0
                if qos:
                    pid = (rbuf[t1] << 8) | rbuf[t1 + 1]
                    t1 += 2
                    if not qos1_ok or pid == 0 or inflight.get(pid) is not None:
                        break  # [MQTT-2.2.1-3] [MQTT-4.3.2-5]
                if inflight.receive_quota == 0 or not state.open:
                    break
                # the frame is taken
                k += 1
                taken += 1
                pk = Packet.inbound_publish(
                    FixedHeader(pkts.PUBLISH, fb == 0x3A, qos, False, f.remaining),
                    topic,
                    bytes(rbuf[t1:end]),
                    pid,
                    4,
                )
                clock = None
                if due:
                    tele.skip_draws(taken - 1 - drawn)
                    clock = tele.publish_clock()
                    drawn = taken
                    sampled = taken + tele.quiet_draws(n - k)
                    if clock is not None:
                        # the decode leg runs from the frame's first
                        # instant, as the per-frame path's does
                        clock.t0 = clock.last = t_frame
                        clock.stamp("decode")
                        pk._tclock = clock
                if not acl(cl, topic, True):
                    self._park_run(cl, items, counted)
                    self._unparked(cl, pk, self._deny_publish)
                    continue
                pk.origin = origin
                pk.created = created
                if expiry:
                    pk.expiry = expiry
                if overload is not None and not overload.admit(cl):
                    self._park_run(cl, items, counted)
                    self._unparked(cl, pk, self._shed_publish)
                    continue
                if clock is not None:
                    self._stamp_admission(pk, clock)
                if qos:
                    cl.write_puback(pid)  # [MQTT-4.3.2-4]
                entry = Parked(complete, clock, None, None, cl, pk)
                entry.loop = here
                entry.counted = counted
                items.append((topic, entry))
        except Code as code:
            self._packet_error(cl, code)
            raise
        finally:
            self._park_run(cl, items, counted)
            if taken:
                last = frames[i + taken - 1]
                info = self.info
                info.bytes_received += last.body_offset + last.remaining - start
                info.packets_received += taken
                info.messages_received += taken
                cl._pub_count += taken
                if tele is not None:
                    tele.skip_draws(taken - drawn)
                ops = self._ops
                ops.ingest_runs += 1
                ops.ingest_run_publishes += taken
        return taken

    def ack_run(
        self, cl: Client, rbuf: bytearray, frames: list, i: int, start: int
    ) -> int:
        """Take in a stretch of one scan's PUBACK frames in one call:
        from ``frames[i]`` on, every frame in turn that is a PUBACK of a
        packet id and nothing else (``clients.ACK_FIRST_BYTE``,
        ``ACK_REMAINING``: v3.1.1's form, v5's without reason or
        properties), each id read straight from ``rbuf``. ``start`` is
        where ``frames[i]`` begins. For the stretch as a whole it does
        what ``process_puback`` and ``process_packet``'s epilogue do a
        frame: every id that is in flight leaves the map
        [MQTT-4.3.2-5], an unknown one is passed over, the send quota
        rises by as many within its maximum and ``info.inflight`` falls
        by them (``Inflight.acknowledge``: one lock pair a run), and
        ``info.bytes_received`` / ``packets_received`` advance as the
        per-frame path advances them. Returns how many frames the
        stretch holds: positive when it took them, all of them;
        negative when the gate is shut and it took none, so that the
        caller sends that many down the per-frame path.

        The gate, on what the code can see and nothing else: a network
        client that is open; no hook that is shown a PUBACK as read, as
        processed or as its delivery's completion (``_ACK_RUN_EVENTS``,
        cached per hooks generation: a storage hook persists
        completions and keeps the per-frame path); no entry of the
        session waiting for send quota, which the quota drain would
        resend between two acks (``Inflight.acknowledge``). With it
        open the drain after each frame finds nothing to do, no frame
        can stop the client, and a PUBACK draws no telemetry clock and
        counts for no tenant: nothing else sees a frame. The frame that
        ends the stretch is not touched."""
        n = len(frames)
        ids = []
        k = i
        while k < n:
            f = frames[k]
            if f.first_byte != ACK_FIRST_BYTE or f.remaining != ACK_REMAINING:
                break
            off = f.body_offset
            ids.append((rbuf[off] << 8) | rbuf[off + 1])
            k += 1
        taken = k - i
        if (
            cl.net.inline
            or cl.closed
            or not self._no_hook_provides("ack_run", _ACK_RUN_EVENTS)
        ):
            return -taken
        removed = cl.state.inflight.acknowledge(ids)
        if removed < 0:
            return -taken
        last = frames[k - 1]
        info = self.info
        info.inflight -= removed
        info.bytes_received += last.body_offset + last.remaining - start
        info.packets_received += taken
        ops = self._ops
        ops.ack_runs += 1
        ops.ack_run_acks += taken
        return taken

    def _park_run(self, cl: Client, items: list, counted: bool) -> None:
        """Park what an ingest run has gathered, in order, before
        anything else happens for its connection, and empty the list.
        The first entry alone may say that nothing of this connection is
        in the stage (``Parked.alone``)."""
        if items:
            if counted:
                items[0][1].alone = not cl._staged
                cl._staged += len(items)
            self._stage.park_many(items)
            del items[:]

    def _unparked(self, cl: Client, pk: Packet, outcome) -> None:
        """``outcome(cl, pk)`` for a publish an ingest run took and does
        not park, and after it what ``process_packet`` does for a packet
        that was not parked: ``on_packet_processed`` with the error the
        outcome raised, if any, and the quota drain."""
        err: Optional[Exception] = None
        try:
            outcome(cl, pk)
        except Exception as e:
            err = e
            raise
        finally:
            self.hooks.on_packet_processed(cl, pk, err)
        self._drain_quota_starved(cl)

    def _complete_staged(self, entries, results, t_set_ns: int = 0) -> None:
        """Complete one slice of a staged batch (``staging.Parked``): fan
        out its publishes in submit order and do for each what follows a
        fan-out: cluster forward, the stage clock, ``on_published``,
        ``on_packet_processed``, the quota drain. Synchronous, on the loop
        that parked the entries.

        What cannot change inside the slice is read once, at its start:
        the hooks' ``provides()`` verdicts, the lazy view class, the
        predicate engine's state, and the slice's target clients, under
        ONE acquisition of the ``clients`` lock (released before any
        delivery, hook or socket write runs). A result that is an
        exception (a host walk that failed) or a publish whose fan-out
        raises is that publish's error: it reaches ``on_packet_processed``
        as ``err``, is recorded against its connection (the read loop
        raises the first one, ``clients.read``) and does not stop the
        slice. ``t_set_ns``: the instant the results were in hand while a
        profiler session keeps the batch (``DeviceProfiler.note_fanout``),
        else 0.

        The slice is the second opener of a socket's cork
        (``Client._cork``; the first is the connection's own read): a
        socket of this loop that the slice targets more than once has
        its cork opened here, its deliveries join it in submit order
        (``_flush_variant``) and leave as ONE write when the slice ends,
        before this method returns and so before any yield to the event
        loop. A socket targeted once is written at its own publish.
        What a corked socket is, is read once too and kept for the slice
        (``clients.SliceSocket``): its deliveries are appends, and the
        counters they move are added when the slice ends. Its QoS>0
        deliveries are begun as one run, settled (``_settle_run``) when
        the slice ends, before its corks are written, unless something
        inside the slice needed the session's inflight state first.

        A result that holds ``$share`` groups has each group's member
        picked here, before the slice reads its targets, when nothing can
        make the pick depend on the publish's own turn: no hook provides
        ON_SELECT_SUBSCRIBERS and no predicate rule is live (rules drop
        failing members before selection). The slice's targets are then
        the members it writes to, not every candidate, and only those are
        probed and corked; the work item's flag tells ``_fan_out`` not to
        pick again. Otherwise every candidate is a target and the pick
        waits for the publish's own turn in ``_fan_out``, in submit
        order."""
        hooks = self.hooks
        observed = hooks.provides(ON_PACKET_ENCODE, ON_PACKET_SENT)
        on_published = (
            hooks.on_published if hooks.provides(ON_PUBLISHED) else None
        )
        on_processed = (
            hooks.on_packet_processed
            if hooks.provides(ON_PACKET_PROCESSED)
            else None
        )
        cluster = self._cluster
        fan_out = self._fan_out
        finish_clock = self._finish_publish_clock
        prof = self.profiler if t_set_ns else None
        # the slice's fan-out plans and, from them, its target clients
        vcls = _view_class()
        eng = self._predicates
        unfiltered = eng is None or not eng.active
        lazy = vcls is not None and unfiltered
        pick_early = unfiltered and not hooks.provides(ON_SELECT_SUBSCRIBERS)
        select_shared = self._select_shared
        ids: list = []
        work = []
        for entry, subs in zip(entries, results):
            targets = None
            early = False
            if type(subs) is vcls:
                if lazy and not subs.has_shared and not subs.has_inline:
                    targets = subs.targets()
                    ids += [cid for cid, _ in targets]
                elif prof is not None and subs.has_shared:
                    # the selection leg's first part (``_select_shared``)
                    t_run = time.perf_counter_ns()
                    subs = subs.materialize()
                    self._ops.share_select_ns += time.perf_counter_ns() - t_run
                else:
                    subs = subs.materialize()
            if targets is None and not isinstance(subs, BaseException):
                if pick_early and subs.shared:
                    subs = select_shared(subs, entry.pk, hooked=False)
                    early = True
                ids += subs.subscriptions  # with the picked members
                if not early:
                    for group in subs.shared.values():
                        ids += group  # every candidate, before selection
            work.append((entry, subs, targets, early))
        if len(ids) > self._ops.slice_targets_max:
            self._ops.slice_targets_max = len(ids)
        present = self.clients.present(ids)
        lookup = present.get
        corked, records = self._cork_repeated(ids, present)
        frames0 = self._ops.cork_frames
        try:
            for entry, subs, targets, early in work:
                cl, pk = entry.cl, entry.pk
                err: Optional[BaseException] = None
                try:
                    if targets is None and isinstance(subs, BaseException):
                        raise subs
                    if prof is not None:
                        t_run = time.perf_counter_ns()
                    fan_out(
                        pk, subs, entry.feats, entry.rjob,
                        lookup, targets, observed, early,
                    )
                    if prof is not None:
                        prof.note_fanout(
                            t_set_ns, t_run, time.perf_counter_ns()
                        )
                    if cluster is not None:
                        cluster.forward_packet(pk)
                    if entry.clock is not None:  # a sampled publish
                        finish_clock(pk)
                    if on_published is not None:
                        on_published(cl, pk)
                except Exception as e:
                    err = e
                if on_processed is not None:
                    try:
                        on_processed(cl, pk, err)
                    except Exception as e:
                        err = err or e
                if err is None:
                    self._drain_quota_starved(cl)
                else:
                    self._staged_error(cl, err, entry.counted)
                if entry.counted:
                    cl._staged -= 1
                    if not cl._staged:
                        # a counted entry was parked from cl.net.loop
                        # and completes on it: the read side's own loop
                        wake = cl._staged_waiter
                        if wake is not None:
                            wake()
        finally:
            if records:
                # the records' runs, each socket's QoS>0 bookkeeping in
                # one settlement: fan-out's time, before the joined
                # writes are timed
                if prof is not None:
                    t_run = time.perf_counter_ns()
                runs = 0
                for rec in records:
                    if rec.entries:
                        runs += 1
                        try:
                            self._settle_run(rec)
                        except Exception as e:
                            self.log.debug(
                                "slice settlement failed: error=%s client=%s",
                                e, rec.cl.id,
                            )
                if prof is not None and runs:
                    prof.note_qos_runs(time.perf_counter_ns() - t_run)
            if corked:
                if prof is not None:
                    span = prof.annotation(
                        "mqtt/loop.flush", sends=len(corked),
                        frames=self._ops.cork_frames - frames0,
                    )
                    span.__enter__()
                    t_run = time.perf_counter_ns()
                # the io counts of the records' deliveries: fan-out's
                # time, as when each delivery added its own
                for rec in records:
                    rec.settle()
                for cl in corked:
                    try:
                        cl._uncork()
                    except Exception as e:
                        self.log.debug(
                            "slice flush failed: error=%s client=%s", e, cl.id
                        )
                if prof is not None:
                    prof.note_slice_flush(time.perf_counter_ns() - t_run)
                    span.__exit__(None, None, None)

    def _cork_repeated(self, ids: list, present: dict) -> tuple[list, list]:
        """Open the cork of every socket a completion slice targets more
        than once (``ids``: the slice's target ids in submit order,
        ``present``: those of them that are connected) and return the
        clients whose cork this call opened: the slice closes them. A
        cork that is open already (the slice runs inside that
        connection's read) stays its opener's; a socket another shard's
        loop owns is written there, outside any slice.

        Those are the sockets whose deliveries leave when the slice
        ends, so what each IS is read here, once: one that is ready, in
        a session that takes shared frames, gets the slice's record
        (``clients.SliceSocket``, on ``Client._slice``), and
        ``_flush_variant`` appends to its cork without asking again.
        The records are returned beside the clients: the slice settles
        their counts, also of those that ended early."""
        hits = list(filter(present.__contains__, ids))
        if len(hits) == len(present):
            return [], []  # every socket once: each is written at its publish
        fabric = self._fabric is not None
        seen: set = set()
        corked = []
        records = []
        for cid in hits:
            if cid not in seen:
                seen.add(cid)
                continue
            cl = present[cid]
            if cl._cork is None and (
                not fabric or self._client_loop_local(cl)
            ):
                cl._cork = bytearray()
                corked.append(cl)
                if not self._session_shares_frames(cl.properties):
                    continue  # its deliveries are rewritten one by one
                self._ops.socket_checks += 1
                try:
                    ready = self._socket_ready(cl)
                except Exception:  # a transport that cannot say
                    ready = False
                if ready:
                    cl._slice = SliceSocket(cl)
                    records.append(cl._slice)
        return corked, records

    def _staged_error(self, cl: Client, err: BaseException, counted: bool) -> None:
        """One staged publish failed in its completion: what
        ``receive_packet`` does for a packet that fails in the handler
        (a Code goes through ``_packet_error``), and the connection's
        first such error is left for its read loop to raise."""
        if isinstance(err, Code):
            self._packet_error(cl, err)
        else:
            self.log.warning(
                "error completing staged publish: error=%r client=%s",
                err, cl.id,
            )
        if counted and cl._staged_err is None:
            cl._staged_err = err

    def _retained_quota_refused(self, cl: Client, pk: Packet) -> bool:
        """Tenant retained COUNT cap (ISSUE 16): True refuses the publish
        with 0x97 before any state grows. Growth only — clearing (empty
        payload) and overwriting an existing retained topic always pass,
        so a capped tenant can still update or free slots. The topic is
        already namespace-scoped here (process_publish scopes first)."""
        t = cl.tenant
        if t is None or not pk.payload:
            return False
        cap = t.max_retained or self.options.tenant_max_retained
        if cap <= 0 or t.retained_count < cap:
            return False
        if self.topics.retained.get(pk.topic_name) is not None:
            return False  # overwrite, not growth
        t.retained_refused += 1
        return True

    def _subscribe_quota_refused(self, cl: Client, sub: Subscription) -> bool:
        """Tenant subscription COUNT cap (ISSUE 16): True refuses the
        filter with 0x97 before any rule or trie registration. Growth
        only — replacing an existing subscription always passes. Sees
        the LOCAL filter (scoping happens in the grant branch); shared
        ($SHARE) filters are uncapped."""
        t = cl.tenant
        if t is None or is_shared_filter(sub.filter):
            return False
        cap = t.max_subscriptions or self.options.tenant_max_subscriptions
        if cap <= 0 or t.subscriptions_count < cap:
            return False
        scoped = ns_scope_filter(t.name, sub.filter)
        if cl.state.subscriptions.get(scoped) is not None:
            return False  # replacement, not growth
        t.subscriptions_refused += 1
        return True

    def retain_message(self, cl: Client, pk: Packet) -> None:
        """(server.go:972-981)"""
        if self.options.capabilities.retain_available == 0 or pk.ignore:
            return
        out = pk.copy(False)
        existed = self.topics.retained.get(out.topic_name) is not None
        r = self.topics.retain_message(out)
        self.hooks.on_retain_message(cl, pk, r)
        self.info.retained = len(self.topics.retained)
        if self._tenancy is not None and out.topic_name[:1] == NS_CHAR:
            t = self._tenancy.tenant_of_topic(out.topic_name)
            if t is not None:
                # durable COUNT quota bookkeeping (ISSUE 16): growth
                # only on a NEW retained topic, shrink on a real clear
                if r == 1 and not existed:
                    t.retained_count += 1
                elif r == -1 and t.retained_count > 0:
                    t.retained_count -= 1
        if self._retained_engine is not None:
            self._retained_engine.note_retained(out.topic_name, r == 1)

    def publish_to_subscribers(self, pk: Packet) -> None:
        """Match subscribers and fan out (server.go:984-1021).

        The synchronous path always walks the host trie: its callers are
        the housekeeping flows ($SYS ticks, LWT, retained delivery, inline
        publishes), which must never pay a device round trip on the event
        loop. Client PUBLISH traffic is parked with the staging loop
        instead when the device matcher is active (``_park_publish``)."""
        if pk.ignore:
            return
        self._stamp_publish_expiry(pk)
        self._fan_out(pk, self.topics.subscribers(pk.topic_name))
        if self._cluster is not None:
            # peer workers with matching subscribers receive the packet
            # once each and fan out locally ($SYS never forwards; retained
            # packets go to all peers) — mqtt_tpu.cluster
            self._cluster.forward_packet(pk)

    def _stamp_publish_expiry(self, pk: Packet) -> None:
        if pk.created == 0:
            pk.created = int(time.time())  # brokerlint: ok=R3 packet creation stamp is wall-clock (persists/expires across restarts)
        if pk.expiry == 0:
            expiry = _minimum(
                self.options.capabilities.maximum_message_expiry_interval,
                pk.properties.message_expiry_interval,
            )
            if expiry > 0:
                pk.expiry = pk.created + expiry

    def fast_publish_eligible(self, cl: Client) -> bool:
        """Session-level gate for the QoS0 passthrough, checked by the
        read loop BEFORE it materializes the frame bytes: v4 network
        client, no staging loop, quota headroom, and no hook that takes
        the packet (the provides() scan is cached per hooks
        generation)."""
        if cl.net.inline or cl.properties.protocol_version != 4:
            return False
        if self._stage is not None or cl.state.inflight.receive_quota == 0:
            return False
        if cl.tenant is not None:
            # tenant publishes need namespace scoping (and possibly the
            # re-encryption leg) — the decode path owns both
            return False
        return self._no_hook_provides("fast_publish", _FAST_PUBLISH_EVENTS)

    def _no_hook_provides(self, gate: str, events: tuple) -> bool:
        """True when no attached hook provides any of ``events``: the
        provides() scan, cached under ``gate`` per hooks generation."""
        gen = self.hooks.generation
        cached = self._hook_gates.get(gate)
        if cached is not None and cached[0] == gen:
            return cached[1]
        ok = not self.hooks.provides(*events)
        # only cache when no add_hook raced the scan: Hooks.add bumps
        # the generation on BOTH sides of the list publish, so a scan
        # that saw a mid-add list can never be cached as current (it
        # still decides this one frame or run — the same window the
        # reference's lock-free hook swap has, hooks.go:150-170)
        if self.hooks.generation == gen:
            self._hook_gates[gate] = (gen, ok)
        return ok

    @staticmethod
    def _shared_frame_ok(props: "ClientProperties", sub: Subscription) -> bool:
        """Target eligibility for shared-frame delivery (nothing forces a
        per-subscriber rewrite of the encoded publish): no positive
        subscription identifiers (zero-valued ones never reach the wire:
        properties.py encodes only v > 0), no outbound aliasing, no size
        cap.

        Used verbatim by BOTH batched fan-out paths (_fan_out_batched's variant/slow
        split and _fan_out_encrypted_batched's shareable gate).
        try_fast_publish intentionally SPLITS the same predicate: the
        subscription half (identifiers) is precomputed into the cached
        fan-out plan, the session half (alias/size, plus its extra
        version==4 requirement) re-checks at delivery because cids can
        reconnect with different properties under a live plan — that
        split is the ONE remaining site that must track rule changes by
        hand."""
        return Server._session_shares_frames(
            props
        ) and Server._subscription_shares_frames(sub)

    @staticmethod
    def _session_shares_frames(props: "ClientProperties") -> bool:
        """The session's half of ``_shared_frame_ok``: fixed at CONNECT,
        so a completion slice reads it once a socket
        (``_cork_repeated``)."""
        return (
            props.props.topic_alias_maximum == 0
            and props.props.maximum_packet_size == 0
        )

    @staticmethod
    def _subscription_shares_frames(sub: Subscription) -> bool:
        """The subscription's half of ``_shared_frame_ok``."""
        ids = sub.identifiers
        return not ids or max(ids.values()) <= 0

    @staticmethod
    def _socket_ready(cl: Client) -> bool:
        """Whether a frame written to this socket now goes out in order
        and at once: the connection is open (a session that outlives its
        socket stays in the registry, its dead writer with it), nothing
        waits in its outbound queue or in the transport's buffer, and no
        TLS layer stands between."""
        writer = cl.net.writer
        return (
            writer is not None
            and cl.state.open
            and cl.state.outbound_qty == 0
            and writer.get_extra_info("sslcontext") is None
            and writer.transport.get_write_buffer_size() == 0
        )

    def _stamp_outbound(self, tcl: Client) -> None:
        """Sampled outbound queue-wait accounting: every successful
        enqueue bumps the client's sequence; 1-in-N also records the
        enqueue time, and the write loop (clients._write_loop) matches
        the sequence on dequeue to observe the wait."""
        st = tcl.state
        st.out_seq += 1
        tele = self.telemetry
        if tele is not None and tele.sample_outbound():
            st.out_stamps.append((st.out_seq, time.perf_counter()))

    def _enqueue_frame(
        self, tcl: Client, data: bytes, pk_source, count_delivery: bool = True
    ) -> bool:
        """Queue a pre-encoded frame on a target's bounded outbound queue;
        False = dropped (queue full) with the shared drop accounting.
        ``pk_source()`` materializes the Packet for on_publish_dropped.
        ``count_delivery`` keeps $SYS housekeeping fan-out out of the
        amplification accounting (the caller knows the topic; the
        pre-encoded frame does not)."""
        tcl._end_slice()  # frames wait in the queue now: not ready
        try:
            tcl.state.outbound.put_nowait(data)
            tcl.state.outbound_full_since = None
            self._stamp_outbound(tcl)
            if count_delivery and self.telemetry is not None:
                # shared-frame delivery WITHOUT an encode — exactly what
                # keeps fan-out amplification near 1
                self.telemetry.fanout_deliveries.inc()
            return True
        except asyncio.QueueFull:
            if tcl.state.outbound_full_since is None:
                # slow-consumer eviction clock (overload SHED posture)
                tcl.state.outbound_full_since = time.monotonic()
            self.info.messages_dropped += 1
            self.hooks.on_publish_dropped(tcl, pk_source())
            return False

    def try_fast_publish(self, cl: Client, frame: bytes, body_offset: int) -> bool:
        """QoS0 v4 PUBLISH frame passthrough — the data-plane fast path.

        Delivers an inbound frame without materializing a ``Packet`` when
        nothing can observe the difference (the same shape Go reaches with
        cheap structs, server.go:857-1021). The caller guarantees first
        byte 0x30 (qos/dup/retain all zero) and that
        ``fast_publish_eligible`` held; this method adds the topic gates —
        plain non-``$`` topic, byte rules kept a strict superset of
        ``is_valid_filter``'s publish rejections (see the cross-reference
        there) — and requires no shared/inline subscribers. The v4 QoS0
        frame is version- and property-free, so inbound bytes equal
        outbound bytes for every shared-frame-eligible target.

        Returns True when fully handled (including an ACL-denied silent
        drop); False defers to the decode path, which owns all error and
        edge-case semantics. Stats mirror ``_decode_body`` +
        ``process_publish``.
        """
        body_len = len(frame) - body_offset
        if body_len < 2:
            return False
        # the frame is relayed VERBATIM, so its remaining-length varint
        # must be minimally encoded (a padded varint like 0x85 0x00 is
        # tolerated by the scanner, but the decode path would re-encode
        # it minimally — an observable difference for strict subscribers)
        if body_offset - 1 != (
            1 if body_len < 128 else 2 if body_len < 16384 else 3 if body_len < 2097152 else 4
        ):
            return False
        tl = (frame[body_offset] << 8) | frame[body_offset + 1]
        t0 = body_offset + 2
        end = t0 + tl
        if tl == 0 or len(frame) < end:
            return False  # empty/truncated topic: decode path raises
        raw = frame[t0:end]
        if b"+" in raw or b"#" in raw or b"\x00" in raw or raw[:1] == b"$":
            return False  # wildcard/$-topic rules live in the slow path
        try:
            topic = raw.decode("utf-8")
        except UnicodeDecodeError:
            return False

        plan = self._plan_for_topic(topic)
        if plan is None:
            return False

        # telemetry stage clock for the passthrough leg: its "decode"
        # stage is near-zero BY DESIGN (the whole point of the fast path
        # is skipping packet materialization) — sampled records make that
        # visible next to the decode path's real cost
        clock = None
        if self.telemetry is not None:
            clock = self.telemetry.publish_clock()
            if clock is not None:
                clock.stamp("decode")

        self.info.packets_received += 1
        self.info.messages_received += 1
        if self.overload is not None and not self.overload.admit(cl):
            # overload shed (mqtt_tpu.overload): the passthrough frame is
            # QoS0 by construction, so the shed is a counted silent drop
            self.info.messages_dropped += 1
            return True
        if not self.hooks.on_acl_check(cl, topic, True):
            return True  # QoS0 deny is a silent drop (server.go:879-881)
        if clock is not None:
            clock.stamp("admission")

        self._fast_fan_frame(plan, topic, frame, body_offset, cl.id)
        if self._cluster is not None:
            # cluster leg: relay the frame verbatim to peer workers with
            # matching subscribers (mqtt_tpu.cluster); write ACL was
            # enforced above, peers apply per-target read ACL. A traced
            # clock rides along so the forward carries the trace id.
            self._cluster.forward_frame(topic, frame, cl.id, clock)
        if clock is not None:
            clock.stamp("fanout")
            self.telemetry.observe_publish(clock, topic, 0)
            if self.telemetry.delivery_sli:
                # the passthrough leg's delivery SLI: tenants never ride
                # this path (fast_publish_eligible), so the label is the
                # global namespace
                self.telemetry.observe_delivery(
                    clock.total(),
                    "",
                    0,
                    "local",
                    trace_id=getattr(clock, "trace_id", None),
                )
        return True

    def _plan_for_topic(self, topic: str):
        """The fast path's fan-out plan, cached per (topic, trie version):
        the walk and the per-subscription identifier scan re-run only
        after a mutation. None means the topic needs the decode path
        (shared/inline subscribers — negative-cached too). Shared by
        try_fast_publish and the cluster's forwarded-frame delivery: any
        change to the shareability predicate applies to both legs."""
        version = self.topics.version
        cached = self._fastpub_plans.get(topic)
        if cached is not None and cached[0] == version:
            return cached[1]
        subscribers = self.topics.subscribers(topic)
        if (
            subscribers.shared
            or subscribers.inline_subscriptions
            or any(
                sub.predicates
                for sub in subscribers.subscriptions.values()
            )
        ):
            # negative-cache: shared/inline topics — and topics with any
            # PREDICATED subscriber, whose delivery depends on each
            # payload — always take the decode path; don't re-walk here
            # on every publish. Version-keyed, so a predicated subscribe
            # (which bumps the trie version) invalidates stale plans.
            if len(self._fastpub_plans) >= 4096:
                self._fastpub_plans.clear()
            self._fastpub_plans[topic] = (version, None)
            return None
        plan = [
            # frame-shareable iff nothing in the SUBSCRIPTION forces a
            # rewrite; the per-SESSION half (version/alias/size) is
            # re-verified at delivery, since cids can reconnect with
            # different properties under the same plan
            (cid, sub, not (sub.identifiers and any(v > 0 for v in sub.identifiers.values())), sub.no_local)
            for cid, sub in subscribers.subscriptions.items()
        ]
        if len(self._fastpub_plans) >= 4096:
            self._fastpub_plans.clear()
        self._fastpub_plans[topic] = (version, plan)
        return plan

    def _fast_fan_frame(
        self, plan, topic: str, frame: bytes, body_offset: int, origin: str
    ) -> None:
        """The fast path's delivery loop over a cached fan-out plan:
        shareable v4 targets get the frame verbatim, everything else takes
        the full per-subscription path. Shared by try_fast_publish and the
        cluster's forwarded-frame delivery."""
        pk: Optional[Packet] = None  # decoded lazily, once, for slow paths

        def pk_source() -> Packet:
            nonlocal pk
            if pk is None:
                pk = self._decode_fast_frame(origin, frame[body_offset:])
            return pk

        clients_get = self.clients.get
        on_acl = self.hooks.on_acl_check
        for cid, sub, shareable, no_local in plan:
            tcl = clients_get(cid)
            if tcl is None or (no_local and cid == origin):
                continue  # [MQTT-3.8.3-3]
            props = tcl.properties
            if (
                shareable
                and props.protocol_version == 4
                and props.props.topic_alias_maximum == 0
                and props.props.maximum_packet_size == 0
            ):
                if not on_acl(tcl, topic, False):
                    continue
                if tcl.net.writer is None or tcl.closed:
                    continue
                self._enqueue_frame(tcl, frame, pk_source)
                continue
            # v5 target / identifiers / alias / size cap: full per-sub path
            try:
                self._deliver_to_client(tcl, sub, pk_source())
            except Exception as e:
                self.log.debug("failed publishing packet: error=%s client=%s", e, cid)

    def fast_deliver_frame(self, frame: bytes, origin: str) -> bool:
        """Deliver a peer-forwarded v4 QoS0 PUBLISH frame to local
        subscribers through the cached fan-out plans (mqtt_tpu.cluster).
        Returns False when this worker needs the decode path for the topic
        (shared/inline subscribers, or a plan miss class). Write ACL was
        enforced at the origin worker."""
        parsed = publish_frame_topic(frame)
        if parsed is None:
            return True  # origin validated it; nothing deliverable here
        topic, body_offset = parsed
        plan = self._plan_for_topic(topic)
        if plan is None:
            return False
        self._fast_fan_frame(plan, topic, frame, body_offset, origin)
        return True

    def _decode_fast_frame(self, origin: str, body: bytes) -> Packet:
        """Materialize the Packet for a fast-path frame that met a
        per-target slow case, stamped exactly like process_publish."""
        pk = Packet(
            fixed_header=FixedHeader(type=pkts.PUBLISH), protocol_version=4
        )
        pk.publish_decode(body)
        pk.origin = origin
        self._stamp_publish_expiry(pk)
        return pk

    def _select_shared(self, subscribers, pk: Packet, hooked: bool = True):
        """One member a shared group (server.go:1005-1012): the hooks'
        pick, else ``Subscribers.select_shared``, folded into the plain
        set. ``hooked``: ask the ON_SELECT_SUBSCRIBERS hooks first; a
        completion slice that knows none provides it picks in its
        prologue without them (``_complete_staged``), and ``_fan_out``
        picks, hooks first, at the publish's own turn otherwise. While a
        profiler session is live the pick is timed into
        ``share_select_ns`` (``_complete_staged`` adds the forced
        materialization that precedes it) inside an ``mqtt/share.select``
        annotation. ``_count_shared`` counts what was picked."""
        prof = self.profiler
        span = None
        if prof is not None and prof.armed:
            span = prof.annotation(
                "mqtt/share.select", groups=len(subscribers.shared)
            )
            span.__enter__()
            t_run = time.perf_counter_ns()
        if hooked:
            subscribers = self.hooks.on_select_subscribers(subscribers, pk)
        if not subscribers.shared_selected:
            subscribers.select_shared()
        subscribers.merge_shared_selected()
        if span is not None:
            self._ops.share_select_ns += time.perf_counter_ns() - t_run
            span.__exit__(None, None, None)
        return subscribers

    def _count_shared(self, subscribers, lookup, early: bool) -> None:
        """Count one publish's picked groups (``_Ops.share_groups``; also
        ``share_groups_early`` where the slice's prologue picked them)
        and the picked members that ``lookup`` finds no connected client
        for (``share_picks_offline``)."""
        ops = self._ops
        groups = len(subscribers.shared)
        ops.share_groups += groups
        if early:
            ops.share_groups_early += groups
        for client in subscribers.shared_selected:
            cl = lookup(client)
            if cl is None or cl.closed:
                ops.share_picks_offline += 1

    def _fan_out(
        self, pk: Packet, subscribers, feats=None, rjob=None,
        lookup=None, targets=None, observed=None, selected=False,
    ) -> None:
        """Deliver one matched publish: shared-group selection, inline
        handlers, per-subscriber delivery (server.go:1000-1021).

        A staged batch's completion (``_complete_staged``) hands in what
        it read once for its slice: ``lookup``, client id -> connected
        Client or None over at least this publish's targets (default:
        ``self.clients.get``, one lock pair a call); ``targets``, the
        lazy view's plan where it has already built it; ``observed``,
        whether a hook provides ON_PACKET_ENCODE / ON_PACKET_SENT
        (default: asked here); ``selected``, the slice picked this
        result's shared groups in its prologue (no selecting hook, no
        live predicate rule), so they are counted here and not picked
        again. Otherwise the groups are picked here, at the publish's own
        turn: after the predicate rules, by the hooks, else by the
        first-member rule.

        Which path serves, decided by what the code sees, never by an
        option: the encode-once batched flush (``_fan_out_batched``)
        unless a hook provides ON_PACKET_ENCODE / ON_PACKET_SENT, which
        must see every subscriber's own packet and so takes the
        per-subscriber loop; lazy views when the C module is present
        and no dict-semantics consumer is ahead (below); compact or
        packed device results by ``TpuMatcher._compact_pays``.

        MQTT+ predicate filtering happens here — the one choke point
        every delivery path funnels through (staged fan-out, the host
        sync path, cluster-forwarded decode deliveries). ``feats`` is
        the publish's PublishFeatures carrier when the staged pipeline
        evaluated the rule table on device (mqtt_tpu.staging); without
        it the host interpreter decides. With no live rules this is one
        attribute read — the unpredicated path stays bit-identical.

        Tenant-namespace publishes (mqtt_tpu.tenancy) strip their scope
        prefix here — every subscriber of a scoped topic is in the same
        tenant BY CONSTRUCTION, so one copy serves the whole fan-out —
        and encrypted-namespace publishes take the batched
        re-encryption leg instead of the shared-frame path (``rjob`` is
        the staged decrypt carrier when the pipeline generated the
        keystream on device).

        Zero-materialization fan-out (ISSUE 13): a lazy
        ``SubscribersView`` result (the device pair stream as the
        currency — native/accelmod.c) is consumed through its
        ``targets()`` plan without ever building the dicts, as long as
        no dict-semantics consumer is ahead (shared groups, inline
        handlers, live predicate rules). Otherwise it materializes
        here, counted, and the eager path serves bit-identically."""
        emissions = ()
        eng = self._predicates
        if lookup is None:
            lookup = self.clients.get
        if observed is None:
            observed = self.hooks.provides(ON_PACKET_ENCODE, ON_PACKET_SENT)
        # ``targets``: the lazy (client_id, Subscription) plan
        if targets is None:
            vcls = _view_class()
            if vcls is not None and type(subscribers) is vcls:
                if (
                    (eng is None or not eng.active)
                    and not subscribers.has_shared
                    and not subscribers.has_inline
                ):
                    targets = subscribers.targets()
                else:
                    subscribers = subscribers.materialize()
        if targets is None:
            if eng is not None and eng.active:
                subscribers, emissions = eng.apply(
                    subscribers, bytes(pk.payload), feats
                )
            if subscribers.shared:
                if not selected:
                    subscribers = self._select_shared(subscribers, pk)
                self._count_shared(subscribers, lookup, selected)

        # tenant namespace: deliveries carry the tenant-LOCAL topic
        # (clients never see the scope prefix); the scoped pk itself
        # stays untouched — the caller still forwards it to the cluster
        dpk = pk
        enc_tenant = None
        if self._tenancy is not None and pk.topic_name[:1] == NS_CHAR:
            dpk = pk.copy(False)
            dpk.topic_name = ns_local(pk.topic_name)
            tenant = self._tenancy.tenant_of_topic(pk.topic_name)
            if (
                self._recrypt is not None
                and tenant is not None
                and tenant.is_encrypted(dpk.topic_name)
            ):
                enc_tenant = tenant

        if enc_tenant is None and targets is None:
            for inline_sub in subscribers.inline_subscriptions.values():
                inline_sub.handler(self.inline_client, inline_sub, dpk)

        if enc_tenant is not None:
            self._fan_out_encrypted(
                enc_tenant, pk, dpk, subscribers, rjob, targets,
                lookup, observed,
            )
        else:
            items = (
                targets
                if targets is not None
                else subscribers.subscriptions.items()
            )
            if not observed:
                # encode-once variant-grouped delivery with the batched
                # GIL-released flush (ISSUE 13 / ROADMAP item 3)
                self._fan_out_batched(pk, dpk, items, lookup)
            else:
                # a hook observes encodes or sends: one encode and one
                # hook call a subscriber
                for id_, subs in items:
                    cl = lookup(id_)
                    if cl is not None:
                        try:
                            delivered = self._deliver_to_client(
                                cl, subs, dpk, account=True
                            )
                        except Exception as e:
                            self.log.debug(
                                "failed publishing packet: error=%s client=%s",
                                e,
                                id_,
                            )
                        else:
                            if delivered and cl.tenant is not None:
                                cl.tenant.messages_out += 1
                                cl.tenant.bytes_out += len(dpk.payload)

        # MQTT+ aggregation windows that completed on this publish emit
        # ONE synthesized publish each (payload = the aggregate), riding
        # the same fan-out tick — no extra timers (mqtt_tpu.predicates)
        for kind, target, sub, agg_payload in emissions:
            out = dpk.copy(False)
            out.payload = agg_payload
            if kind == "inline":
                try:
                    target.handler(self.inline_client, target, out)
                except Exception as e:
                    self.log.debug("inline aggregate handler failed: %s", e)
                continue
            cl = self.clients.get(target)
            if cl is not None:
                try:
                    self._deliver_to_client(cl, sub, out)
                except Exception as e:
                    self.log.debug(
                        "failed publishing aggregate: error=%s client=%s",
                        e,
                        target,
                    )

    def _fan_out_batched(self, pk: Packet, dpk: Packet, items, lookup) -> None:
        """Encode-once variant-grouped fan-out (ISSUE 13 / ROADMAP item
        3). Targets are grouped by (protocol version, effective QoS,
        retain) — the complete set of per-target wire differences once
        aliasing/size-caps/positive-identifier sessions are excluded —
        and each variant's frame is encoded ONCE. QoS>0 targets get
        their packet id patched inside the batched native flush (writev
        iovecs, GIL released across the whole delivery batch); targets
        whose session forces a per-subscriber rewrite take the legacy
        path. Per-socket backpressure (bounded outbound queues), the
        slow-consumer eviction clock and every drop/overload counter
        behave exactly as the legacy path — only the encode count and
        the GIL profile change.

        A target whose socket the completion slice in hand keeps a
        record of (``Client._slice``: corked by the slice and read once
        when the slice began) has the session's half of
        ``_shared_frame_ok`` and its protocol version on the record;
        ``_flush_variant`` appends its frame to the cork without reading
        the socket again."""
        clock = getattr(pk, "_tclock", None)
        topic = dpk.topic_name
        sys_topic = topic.startswith("$SYS")
        amp_tele = None if sys_topic else self.telemetry
        origin = dpk.origin
        header = dpk.fixed_header
        retained = header.retain
        qos = header.qos
        if qos and qos > self.options.capabilities.maximum_qos:
            qos = self.options.capabilities.maximum_qos  # [MQTT-3.2.2-9]
        subscription_ok = self._subscription_shares_frames
        # a variant's key: version | effective QoS << 3 | retain << 5
        groups: dict[int, list] = {}
        slow: list = []
        for cid, sub in items:
            cl = lookup(cid)
            if cl is None or (sub.no_local and cid == origin):
                continue  # [MQTT-3.8.3-3]
            rec = cl._slice
            if rec is None:
                props = cl.properties
                if not self._shared_frame_ok(props, sub):
                    slow.append((cl, sub))
                    continue
                key = props.protocol_version
            elif subscription_ok(sub):
                key = rec.version
            else:
                slow.append((cl, sub))
                continue
            if retained and (
                sub.fwd_retained_flag
                or (key == 5 and sub.retain_as_published)
            ):  # [MQTT-3.3.1-12] / [MQTT-3.3.1-13]
                key |= 32
            key |= (qos if qos <= sub.qos else sub.qos) << 3
            groups.setdefault(key, []).append((cl, sub))

        variants = [
            (key, self._encode_variant(dpk, key, amp_tele), group)
            for key, group in groups.items()
        ]
        if clock is not None:
            clock.stamp("encode")

        for key, (data, id_off), group in variants:
            self._flush_variant(
                dpk, key >> 3 & 3, key >= 32, data, id_off, group, sys_topic
            )
        for cl, sub in slow:
            try:
                delivered = self._deliver_to_client(
                    cl, sub, dpk, account=True
                )
            except Exception as e:
                self.log.debug(
                    "failed publishing packet: error=%s client=%s", e, cl.id
                )
            else:
                if delivered and cl.tenant is not None:
                    cl.tenant.messages_out += 1
                    cl.tenant.bytes_out += len(dpk.payload)
        if clock is not None:
            clock.stamp("flush")

    def _encode_variant(self, dpk: Packet, key: int, amp_tele) -> tuple:
        """One variant of a publish, encoded once for all its targets:
        ``(frame, offset of the packet id in it or -1)``. ``key`` as
        ``_fan_out_batched`` makes it."""
        eff = key >> 3 & 3
        out = dpk.copy(False)
        out.fixed_header.qos = eff
        out.fixed_header.retain = key >= 32
        out.protocol_version = key & 7
        if eff > 0:
            # nonzero placeholder (the encoder rejects pid 0 on
            # QoS>0); every target's real id is patched at flush
            out.packet_id = 1
        if out.expiry > 0:
            # the send-time expiry rewrite [MQTT-3.3.2-6], once per
            # variant instead of per subscriber
            out.properties.message_expiry_interval = max(
                1, out.expiry - int(time.time())  # brokerlint: ok=R3 message expiry is an absolute wall-clock stamp
            )
        buf = get_buffer()
        try:
            pkts.ENCODERS[pkts.PUBLISH](out, buf)
            data = bytes(buf)
        finally:
            put_buffer(buf)
        if amp_tele is not None:
            amp_tele.publish_encodes.inc()
            amp_tele.fanout_variants.inc()
        id_off = -1
        if eff > 0:
            # packet id sits right after the topic in the variable
            # header (no aliasing in this path, so the topic is
            # always present)
            id_off = (
                publish_frame_body_offset(data)
                + 2
                + len(dpk.topic_name.encode("utf-8"))
            )
        return data, id_off

    def _flush_variant(
        self,
        dpk: Packet,
        eff: int,
        retain: bool,
        data: bytes,
        id_off: int,
        group: list,
        sys_topic: bool,
    ) -> None:
        """Deliver one encoded variant to its target group: ready
        sockets (idle transport + empty outbound queue, no TLS) flush
        through ONE GIL-released native call; everything else rides the
        bounded outbound queue with the existing backpressure, eviction
        and drop accounting. A ready socket whose cork is open
        (``Client._cork``: the completion slice in hand targets it again,
        or its own read is in hand) takes the frame into the cork, in
        order behind what it holds, and is written when the cork's
        opener closes it. A socket the completion slice in hand keeps a
        record of (``Client._slice``, ``clients.SliceSocket``: corked by
        the slice and found ready then) is not read again: past the ACL
        call and the QoS bookkeeping its delivery is one append to that
        cork. Where no hook provides ``on_qos_publish``, a QoS>0
        delivery to such a socket appends its frame with the variant's
        placeholder id and joins the record's run: the socket's QoS>0
        deliveries of the slice are begun together, under one hold of
        the session's inflight lock, and their ids written into the
        cork (``_settle_run``), before anything else reads the session's
        inflight state and before the cork is written. Whatever else
        touches the socket inside the slice ends the record, a hook of
        this very delivery too, and the socket is read as any other. The
        three ways out are counted, a delivery each where it was accepted
        (``_Ops.deliveries_flush``, ``_cork``, ``_queue``), and the
        deliveries a full queue refused (``deliveries_dropped_full``).

        Under the shard fabric the group is split BY OWNING SHARD
        first: each remote shard receives its whole sub-group as one
        marshaled call of this same method — the encode already
        happened once on the publishing shard, and the remote shard
        runs eligibility, QoS bookkeeping and its own ONE native flush
        loop-locally (ISSUE 15: whole per-shard delivery batches into
        the encode-once write path). ``call_soon_threadsafe`` preserves
        per-publisher FIFO into each shard, so one publisher's
        deliveries to one subscriber stay in order."""
        if self._fabric is not None:
            try:
                here: Optional[asyncio.AbstractEventLoop] = (
                    asyncio.get_running_loop()
                )
            except RuntimeError:
                here = None
            local: list = []
            remote: dict = {}
            for cl, sub in group:
                loop = cl.net.loop
                if loop is None or loop is here:
                    local.append((cl, sub))
                else:
                    remote.setdefault(loop, []).append((cl, sub))
            for loop, rgroup in remote.items():
                try:
                    loop.call_soon_threadsafe(
                        self._flush_variant,
                        dpk, eff, retain, data, id_off, rgroup, sys_topic,
                    )
                except RuntimeError:
                    continue  # shard gone; its clients are going away
            if not local:
                return
            group = local

        count_delivery = not sys_topic
        topic = dpk.topic_name
        if topic[:1] == NS_CHAR:
            topic = ns_local(topic)
        on_acl = self.hooks.on_acl_check
        payload_len = len(dpk.payload)
        prof = self.profiler
        armed = prof is not None and prof.armed
        if eff > 0:
            # what every QoS>0 delivery of the variant reads alike
            caps = self.options.capabilities
            cap, max_id = caps.maximum_inflight, caps.maximum_packet_id
            hooked = not self._no_hook_provides(
                "qos_publish", _QOS_PUBLISH_EVENTS
            )
            on_qos_publish = self.hooks.on_qos_publish
            info = self.info
            proto: Optional[Packet] = None
            n_begins = n_hooked = begin_ns = 0
        flush: list = []
        n_checks = n_cork = n_queue = n_full = n_rec = 0
        for cl, sub in group:
            try:
                if not on_acl(cl, topic, False):
                    continue
                # the slice's record of this socket, if it holds still:
                # open and ready, read when the slice corked it
                rec = cl._slice
                if rec is None and (cl.closed or cl.net.writer is None):
                    continue
                pid = 0
                if eff > 0:
                    # the QoS>0 bookkeeping of publish_to_client: the
                    # entry is a clone of the variant's prototype, and
                    # the session's inflight lock is taken once
                    if armed:
                        t_begin = time.perf_counter_ns()
                    if proto is None:
                        proto = dpk.copy(False)
                        proto.topic_name = topic
                        proto.fixed_header.qos = eff
                        proto.fixed_header.retain = retain
                    out = proto.clone()
                    n_begins += 1
                    if rec is not None and not hooked:
                        # the socket's run: the frame joins the cork
                        # with its placeholder id, and the lock, the id
                        # and the store wait for the run's settlement
                        # (_settle_run), once a socket a slice
                        if armed:
                            begin_ns += time.perf_counter_ns() - t_begin
                        rec.take_run(data, id_off, payload_len, out, dpk)
                        n_rec += 1
                        continue
                    # a pending run of the socket takes its ids first
                    st = cl.state
                    pid = cl.settled_inflight().begin(st, out, cap, max_id)
                    if pid >= BEGIN_PARKED:  # a new entry
                        info.inflight += 1
                        if hooked:
                            n_hooked += 1
                            on_qos_publish(cl, out, out.created, 0)
                        if pid == BEGIN_PARKED:
                            out.expiry = -1  # resent once quota frees
                    else:
                        self._qos_refused(cl, dpk, pid)
                    if armed:
                        begin_ns += time.perf_counter_ns() - t_begin
                    if pid < 0:
                        continue  # quota-refused or parked for resend
                if rec is not None and cl._slice is rec:
                    # ONE append, behind what the cork holds; the io
                    # counts wait for the slice's end (SliceSocket.settle)
                    rec.take(
                        data if id_off < 0
                        else self._patch_id(data, id_off, pid),
                        payload_len,
                    )
                    n_rec += 1
                    continue
                fd = -1
                n_checks += 1
                if self._socket_ready(cl):
                    if cl._cork is not None:
                        # an open cork (the slice targets this socket
                        # again, or its own read is in hand): the frame
                        # joins it, behind what it holds
                        frame = (
                            data if id_off < 0
                            else self._patch_id(data, id_off, pid)
                        )
                        if self._transport_write_frame(
                            cl, frame, count_delivery
                        ):
                            n_cork += 1
                            self._note_tenant_out(cl, dpk)
                        continue
                    sock = cl.net.writer.get_extra_info("socket")
                    if sock is not None:
                        try:
                            fd = sock.fileno()
                        except OSError:
                            fd = -1
                if fd >= 0:
                    # tenant accounting deferred to the flush outcome
                    flush.append((cl, fd, pid))
                    continue
                frame = (
                    data if id_off < 0
                    else self._patch_id(data, id_off, pid)
                )
                if not self._enqueue_frame(
                    cl, frame, lambda: dpk,
                    count_delivery=count_delivery,
                ):
                    n_full += 1
                    if eff > 0:
                        self._rollback_qos_delivery(cl, pid)
                    continue
                n_queue += 1
            except Exception as e:
                self.log.debug(
                    "failed publishing packet: error=%s client=%s", e, cl.id
                )
                continue
            self._note_tenant_out(cl, dpk)
        ops = self._ops
        if n_rec:
            # what a profiler's snapshot reads off the loop moves with
            # the publish, as its fanout_n does
            n_cork += n_rec
            ops.cork_frames += n_rec
            if count_delivery and self.telemetry is not None:
                self.telemetry.fanout_deliveries.inc(n_rec)
        ops.socket_checks += n_checks
        ops.deliveries_cork += n_cork
        ops.deliveries_queue += n_queue
        ops.deliveries_dropped_full += n_full
        if eff > 0:
            ops.qos_begins += n_begins
            ops.qos_begins_hooked += n_hooked
            ops.qos_begin_ns += begin_ns
        if not flush:
            return
        from .native import fan_flush

        if armed:
            t_send = time.perf_counter_ns()
        sent = fan_flush(
            [fd for _, fd, _ in flush],
            data,
            id_off,
            [pid for _, _, pid in flush] if id_off >= 0 else None,
        )
        if armed:  # the sends of all of the variant's sockets
            prof.send_busy_ns += time.perf_counter_ns() - t_send
        if self.telemetry is not None:
            self.telemetry.fanout_writev_batches.inc()
        if sent is None:
            # no native library: encode-once still holds, delivery goes
            # through the per-target transport write
            for cl, _fd, pid in flush:
                frame = (
                    data if id_off < 0 else self._patch_id(data, id_off, pid)
                )
                if self._transport_write_frame(cl, frame, count_delivery):
                    ops.deliveries_flush += 1
                    self._note_tenant_out(cl, dpk)
            return
        n = len(data)
        for (cl, _fd, pid), wrote in zip(flush, sent.tolist()):
            if wrote == n:
                ops.socket_sends += 1  # the native flush's send
                self._note_direct_write(cl, n, count_delivery)
            elif wrote >= 0:
                # short write (kernel buffer filled mid-frame): finish
                # through the transport — ordering-safe, the transport
                # buffer was empty and we never left the loop thread
                frame = (
                    data if id_off < 0 else self._patch_id(data, id_off, pid)
                )
                try:
                    cl.net.writer.write(frame[wrote:])
                    ops.socket_sends += 2  # the flush's and the tail's
                    cl.note_held()
                except Exception as e:
                    self.log.debug(
                        "fan-out flush tail failed: error=%s client=%s",
                        e, cl.id,
                    )
                    continue
                self._note_direct_write(cl, n, count_delivery)
            else:
                # -errno (EAGAIN-before-anything, or the connection is
                # going away): the transport path owns delivery + errors
                frame = (
                    data if id_off < 0 else self._patch_id(data, id_off, pid)
                )
                if not self._transport_write_frame(
                    cl, frame, count_delivery
                ):
                    continue
            # accounting only on a delivery that actually went out (the
            # legacy path counts after publish_to_client succeeds)
            ops.deliveries_flush += 1
            self._note_tenant_out(cl, dpk)

    def _settle_run(self, rec: SliceSocket) -> None:
        """Settle the run of a slice's record of one socket
        (``SliceSocket.entries``, ``.marks``: its QoS>0 deliveries, each
        one's frame in the cork with a placeholder id): what
        ``_flush_variant`` does a delivery with ``Inflight.begin``, for
        all of them under one acquisition of the session's inflight lock
        (``Inflight.begin_run``). Each accepted entry's id is written into
        its frame's two bytes in the cork; a parked entry is marked for
        resend; the frames of parked and refused deliveries are cut out
        of the cork (``_cut_frames``); each refusal is then told
        (``_qos_refused``), in delivery order. Entries, ids, cursor,
        quota and bytes come out as the per-delivery bookkeeping leaves
        them. Where the bookkeeping fails, every frame of the run is cut
        out: none leaves with its placeholder id."""
        entries, rec.entries = rec.entries, []
        marks, rec.marks = rec.marks, []
        prof = self.profiler
        armed = prof is not None and prof.armed
        if armed:
            t0 = time.perf_counter_ns()
        cl = rec.cl
        st = cl.state
        inflight = st.inflight
        caps = self.options.capabilities
        try:
            answers = inflight.begin_run(
                st, entries, caps.maximum_inflight, caps.maximum_packet_id
            )
        except Exception as e:
            self.log.debug(
                "failed settling a run: error=%s client=%s", e, cl.id
            )
            self._cut_frames(rec, marks)
            # what the failed call stored stays, for resend
            self.info.inflight += sum(
                inflight.get(out.packet_id) is out for out in entries
            )
            return
        cork = rec.cork
        dropped: list = []
        refused: list = []
        for pid, entry, mark in zip(answers, entries, marks):
            if pid > 0:
                at = mark[1] + mark[2]
                cork[at] = pid >> 8
                cork[at + 1] = pid & 0xFF
                continue
            dropped.append(mark)
            if pid == BEGIN_PARKED:
                entry.expiry = -1  # resent once quota frees
            else:
                refused.append((mark[0], pid))
        if dropped:
            self._cut_frames(rec, dropped)
        self.info.inflight += len(answers) - len(refused)
        ops = self._ops
        ops.qos_runs += 1
        ops.qos_run_entries += len(entries)
        for pk, code in refused:
            try:
                self._qos_refused(cl, pk, code)
            except Exception as e:
                self.log.debug(
                    "failed publishing packet: error=%s client=%s", e, cl.id
                )
        if armed:
            ops.qos_begin_ns += time.perf_counter_ns() - t0

    def _cut_frames(self, rec: SliceSocket, marks: list) -> None:
        """Cut the frames ``marks`` names (``SliceSocket.marks``, in cork
        order) out of the record's cork, back to front, and take back the
        counts their appends moved: on the loop that moved them, inside
        the same slice."""
        cork = rec.cork
        ops = self._ops
        tele = self.telemetry
        for pk, at, _id_off, n in reversed(marks):
            del cork[at:at + n]
            rec.n -= 1
            rec.nbytes -= n
            rec.payload -= len(pk.payload)
            ops.cork_frames -= 1
            ops.deliveries_cork -= 1
            if tele is not None and not pk.topic_name.startswith("$SYS"):
                tele.fanout_deliveries.inc(-1)

    @staticmethod
    def _patch_id(data: bytes, id_off: int, pid: int) -> bytes:
        """A copy of the variant frame with this target's packet id."""
        b = bytearray(data)
        b[id_off] = (pid >> 8) & 0xFF
        b[id_off + 1] = pid & 0xFF
        return bytes(b)

    def _qos_refused(self, cl: Client, pk: Packet, code: int) -> None:
        """What a QoS>0 delivery that ``Inflight.begin`` refused (``code``:
        BEGIN_FULL or BEGIN_EXHAUSTED) leaves behind: the drop counted,
        a warning, and on exhausted packet ids the hooks told first."""
        if code == BEGIN_FULL:
            self.info.inflight_dropped += 1
            self.log.warning(
                "client store quota reached: client=%s listener=%s",
                cl.id, cl.net.listener,
            )
            return
        self.hooks.on_packet_id_exhausted(cl, pk)
        self.info.inflight_dropped += 1
        self.log.warning(
            "packet ids exhausted: client=%s listener=%s",
            cl.id, cl.net.listener,
        )

    def _rollback_qos_delivery(self, cl: Client, pid: int) -> None:
        """Undo a delivery's ``Inflight.begin`` after a failed enqueue —
        the exact rollback publish_to_client performs on a full outbound
        queue."""
        cl.state.inflight.delete(pid)
        cl.state.inflight.increase_send_quota()

    def _note_direct_write(
        self, cl: Client, nbytes: int, count_delivery: bool
    ) -> None:
        """Accounting for one frame the native flush delivered — the
        union of clients.write_frame's io counters and _enqueue_frame's
        delivery count. Frames only: the caller counts the sends
        (``_Ops.socket_sends``)."""
        self.info.bytes_sent += nbytes
        self.info.packets_sent += 1
        self.info.messages_sent += 1
        st = cl.state
        st.out_bytes += nbytes
        st.out_writes += 1
        tele = self.telemetry
        if tele is not None:
            tele.outbound_bytes.inc(nbytes)
            tele.outbound_writes.inc()
            if count_delivery:
                tele.fanout_deliveries.inc()

    @staticmethod
    def _note_tenant_out(cl: Client, dpk: Packet) -> None:
        """Per-tenant outbound accounting for one completed delivery."""
        if cl.tenant is not None:
            cl.tenant.messages_out += 1
            cl.tenant.bytes_out += len(dpk.payload)

    def _transport_write_frame(
        self, cl: Client, frame: bytes, count_delivery: bool
    ) -> bool:
        """Fallback delivery of a pre-encoded frame through the asyncio
        transport (native flush unavailable or refused the socket);
        False = the write was not accepted."""
        try:
            cl.write_frame(frame)
        except Exception as e:
            self.log.debug(
                "failed publishing packet: error=%s client=%s", e, cl.id
            )
            return False
        if count_delivery and self.telemetry is not None:
            self.telemetry.fanout_deliveries.inc()
        return True

    def _key_idents(self, cid: str, cl: Optional[Client] = None) -> tuple:
        """The key-identity candidates for a client id: the tenant-LOCAL
        client id first, then the connected client's username — whatever
        the operator keyed the tenant's key map on (mqtt_tpu.tenancy)."""
        from .tenancy import local_client_id

        if cl is None:
            cl = self.clients.get(cid)
        uname = ""
        if cl is not None:
            u = cl.properties.username
            uname = (
                u.decode("utf-8", "replace")
                if isinstance(u, (bytes, bytearray))
                else (u or "")
            )
        return (local_client_id(cid), uname)

    def _origin_idents(self, pk: Packet) -> tuple:
        """Key-identity candidates for a publish's ORIGIN: the live
        session's identities plus the username rider cluster forwards
        carry (mqtt_tpu.cluster head["u"]) — a username-keyed publisher
        must resolve on workers where its session does not exist."""
        idents = self._key_idents(pk.origin)
        rider = getattr(pk, "_origin_user", "")
        if rider and rider not in idents:
            idents = idents + (rider,)
        return idents

    def _recrypt_job_for(self, cl: Client, pk: Packet):
        """The staged decrypt carrier for an encrypted-namespace publish
        (None for everything else). Built at submit time so the
        keystream dispatch rides the match batch (mqtt_tpu.staging)."""
        renc = self._recrypt
        tenant = cl.tenant
        if renc is None or tenant is None:
            return None
        local = ns_local(pk.topic_name)
        if not tenant.is_encrypted(local):
            return None
        return renc.decrypt_job(
            tenant, self._key_idents(pk.origin, cl), bytes(pk.payload)
        )

    def _fan_out_encrypted(
        self, tenant, pk: Packet, dpk: Packet, subscribers, rjob,
        targets, lookup, observed,
    ) -> None:
        """The MQT-TZ re-encryption fan-out (mqtt_tpu.tenancy): decrypt
        the publish once with the publisher's key (the staged keystream
        when the batch rode the device, the host path otherwise),
        re-encrypt per subscriber in ONE batched keystream dispatch, and
        deliver each subscriber its own ``nonce || ciphertext``. Keyless
        subscribers receive nothing (counted) — an encrypted namespace
        never leaks plaintext or someone else's ciphertext.

        ``targets`` is the lazy view's (client_id, Subscription) plan
        when the zero-materialization path resolved this publish — the
        encrypted leg consumes sid pairs directly too (ISSUE 13).
        Shareable-QoS0 targets additionally skip the per-subscriber
        Packet+encode entirely: one shared frame HEAD is encoded per
        (version, retain) variant and the native layer assembles
        ``head || nonce_i || ciphertext_i`` frames from the batched
        keystream XOR in a single pass (PR 12 residual closed for the
        host path)."""
        renc = self._recrypt
        plaintext = renc.open_publish(
            tenant, self._origin_idents(pk), bytes(pk.payload), rjob
        )
        if plaintext is None:
            # keyless publisher / malformed framing: the publish is
            # undeliverable (engine counters carry the reason)
            self.info.messages_dropped += 1
            tenant.messages_dropped += 1
            return
        items = (
            list(targets)
            if targets is not None
            else list(subscribers.subscriptions.items())
        )
        if not observed:
            if self._fan_out_encrypted_batched(
                tenant, dpk, plaintext, items, lookup
            ):
                return
        key_targets = [
            (cid, self._key_idents(cid, lookup(cid))) for cid, _sub in items
        ]
        sealed = renc.seal_fanout(tenant, plaintext, key_targets)
        for id_, subs in items:
            data = sealed.get(id_)
            if data is None:
                continue  # keyless subscriber: withheld, counted
            cl = lookup(id_)
            if cl is None:
                continue
            out = dpk.copy(False)
            out.payload = data
            try:
                delivered = self._deliver_to_client(
                    cl, subs, out, account=True
                )
            except Exception as e:
                self.log.debug(
                    "failed publishing recrypted packet: error=%s "
                    "client=%s",
                    e,
                    id_,
                )
            else:
                if delivered:
                    tenant.messages_out += 1
                    tenant.bytes_out += len(data)

    def _fan_out_encrypted_batched(
        self, tenant, dpk: Packet, plaintext: bytes, items: list, lookup
    ) -> bool:
        """The re-encrypt fan-out's encode-once leg (ISSUE 13 satellite,
        PR 12 residual): ONE keystream dispatch for every keyed target,
        then per-subscriber frames assembled in C as ``head || nonce_i
        || (plaintext XOR keystream_i)`` — the frame HEAD is encoded
        once per (version, retain) variant, so encrypted namespaces no
        longer pay a per-subscriber Packet copy + encode. Targets whose
        session forces a per-subscriber rewrite (QoS>0, aliasing, size
        caps, positive identifiers) still ride publish_to_client with
        their sealed payloads — same keystream dispatch, no second one.
        Returns True when delivery was fully handled here."""
        from .native import assemble_frames

        renc = self._recrypt
        caps = self.options.capabilities
        origin = dpk.origin
        live: list = []  # (cid, cl, sub, eff, pv, retain, shareable)
        for cid, sub in items:
            cl = lookup(cid)
            if cl is None or (sub.no_local and cid == origin):
                continue
            props = cl.properties
            eff = dpk.fixed_header.qos
            if eff > sub.qos:
                eff = sub.qos
            if eff > caps.maximum_qos:
                eff = caps.maximum_qos
            pv = props.protocol_version
            retain = dpk.fixed_header.retain and (
                sub.fwd_retained_flag
                or (pv == 5 and sub.retain_as_published)
            )
            shareable = eff == 0 and self._shared_frame_ok(props, sub)
            live.append((cid, cl, sub, eff, pv, bool(retain), shareable))
        if not any(s for *_x, s in live):
            return False  # nothing shareable: the legacy path is simpler
        raw = renc.seal_fanout_raw(
            tenant, plaintext,
            [(cid, self._key_idents(cid, cl)) for cid, cl, *_r in live],
        )
        if raw is None:
            # keyless everything: withheld (counted by the engine)
            return True
        keyed, nonces, rows = raw
        kmap = {tkey: i for i, (tkey, _kid) in enumerate(keyed)}
        n_blocks = (len(plaintext) + 15) // 16
        ks2d = (
            rows.reshape(len(keyed), n_blocks * 16)
            if rows is not None
            else None
        )
        payload_len = renc.nonce_bytes + len(plaintext)

        # group shareable targets by head variant; deliver the rest
        # per-subscriber with their sealed payload slices
        groups: dict[tuple, list] = {}
        import numpy as _np

        pt_arr = _np.frombuffer(plaintext, dtype=_np.uint8)
        for cid, cl, sub, eff, pv, retain, shareable in live:
            ki = kmap.get(cid)
            if ki is None:
                continue  # keyless subscriber: withheld, counted
            if shareable:
                groups.setdefault((pv, retain), []).append((cl, ki))
                continue
            data = nonces[ki].tobytes() + (
                (ks2d[ki][: len(plaintext)] ^ pt_arr).tobytes()
                if ks2d is not None
                else b""
            )
            out = dpk.copy(False)
            out.payload = data
            try:
                delivered = self._deliver_to_client(
                    cl, sub, out, account=True
                )
            except Exception as e:
                self.log.debug(
                    "failed publishing recrypted packet: error=%s "
                    "client=%s", e, cid,
                )
            else:
                if delivered:
                    tenant.messages_out += 1
                    tenant.bytes_out += len(data)

        amp_tele = self.telemetry
        # the tenant-LOCAL topic (what the subscriber subscribed to):
        # the ACL below must judge what the client sees on the wire
        topic = dpk.topic_name
        if topic[:1] == NS_CHAR:
            topic = ns_local(topic)
        for (pv, retain), group in groups.items():
            out = dpk.copy(False)
            out.fixed_header.qos = 0
            out.fixed_header.retain = retain
            out.protocol_version = pv
            out.payload = b"\x00" * payload_len  # placeholder bytes only
            if out.expiry > 0:
                out.properties.message_expiry_interval = max(
                    1, out.expiry - int(time.time())  # brokerlint: ok=R3 message expiry is an absolute wall-clock stamp
                )
            buf = get_buffer()
            try:
                pkts.ENCODERS[pkts.PUBLISH](out, buf)
                frame = bytes(buf)
            finally:
                put_buffer(buf)
            head = frame[: len(frame) - payload_len]
            if amp_tele is not None:
                amp_tele.publish_encodes.inc()
                amp_tele.fanout_variants.inc()
            idxs = [ki for _cl, ki in group]
            frames = None
            if ks2d is not None:
                frames = assemble_frames(
                    head, nonces[idxs], ks2d[idxs], plaintext
                )
            if frames is None:
                # no native library (or empty plaintext): numpy assembly,
                # still encode-once
                ct = (
                    (ks2d[idxs][:, : len(plaintext)] ^ pt_arr[None, :])
                    if ks2d is not None
                    else _np.zeros((len(idxs), 0), dtype=_np.uint8)
                )
                rows_bytes = [
                    head + nonces[ki].tobytes() + ct[i].tobytes()
                    for i, ki in enumerate(idxs)
                ]
            else:
                rows_bytes = [f.tobytes() for f in frames]
            for (cl, _ki), fbytes in zip(group, rows_bytes):
                try:
                    # the per-target read ACL every delivery path
                    # enforces (publish_to_client raises on the slow
                    # legs; here denial withholds the frame)
                    if not self.hooks.on_acl_check(cl, topic, False):
                        continue
                    if cl.closed or cl.net.writer is None:
                        continue
                    if self._enqueue_frame(cl, fbytes, lambda: dpk):
                        tenant.messages_out += 1
                        tenant.bytes_out += payload_len
                except Exception as e:
                    self.log.debug(
                        "failed publishing recrypted packet: error=%s "
                        "client=%s", e, cl.id,
                    )
        return True

    def _client_loop_local(self, cl: Client) -> bool:
        """True when the calling thread may touch this client's
        loop-affine state directly (its owning loop, or no loop)."""
        loop = cl.net.loop
        if loop is None:
            return True
        try:
            return loop is asyncio.get_running_loop()
        except RuntimeError:
            return False

    def _deliver_to_client(
        self,
        cl: Client,
        sub: Subscription,
        pk: Packet,
        account: bool = False,
    ) -> bool:
        """``publish_to_client`` with shard-loop affinity (mqtt_tpu.shards):
        a delivery that mutates per-client loop-affine state (QoS>0
        packet-id/inflight bookkeeping, outbound topic aliasing) for a
        client ANOTHER shard owns is marshaled onto that shard's loop;
        everything else — the shared-frame and plain QoS0 paths, whose
        only cross-thread touch is the thread-safe outbound queue —
        runs inline. No fabric = always inline = today's path.

        Returns True when the delivery ran inline (exceptions propagate
        and the caller does its own accounting); False when marshaled
        (the owner-loop callback logs failures and, with ``account``,
        performs the tenant accounting itself)."""
        if self._fabric is None or self._client_loop_local(cl):
            self.publish_to_client(cl, sub, pk)
            return True
        eff = pk.fixed_header.qos
        if eff > sub.qos:
            eff = sub.qos
        if eff == 0 and cl.properties.props.topic_alias_maximum == 0:
            self.publish_to_client(cl, sub, pk)
            return True
        loop = cl.net.loop
        try:
            loop.call_soon_threadsafe(  # type: ignore[union-attr]
                self._deliver_remote, cl, sub, pk, account
            )
        except RuntimeError:
            pass  # owner shard gone; the client is going away with it
        return False

    def _deliver_remote(
        self,
        cl: Client,
        sub: Subscription,
        pk: Packet,
        account: bool,
    ) -> None:
        """The owner-shard half of a marshaled delivery."""
        if _LOOP_PLANE.active:
            w = _LOOP_PLANE.witness
            if w is not None:
                # call_soon_threadsafe landed us on the owner's loop;
                # anything else is a marshal-routing bug
                w.check_owner(
                    "client_state", "deliver_marshal", cl.net.loop,
                    detail=cl.id,
                )
        try:
            self.publish_to_client(cl, sub, pk)
        except Exception as e:
            self.log.debug(
                "failed publishing packet: error=%s client=%s", e, cl.id
            )
        else:
            if account:
                self._note_tenant_out(cl, pk)

    def publish_to_client(
        self,
        cl: Client,
        sub: Subscription,
        pk: Packet,
    ) -> Packet:
        """Deliver one publish to one subscriber (server.go:1023-1113).

        A namespace-scoped ``pk`` (retained deliveries walk the trie
        directly, so their packets still carry the tenant prefix —
        mqtt_tpu.tenancy) is delivered under its tenant-LOCAL topic:
        the ACL, aliasing, and the wire all see what the client
        subscribed to."""
        if sub.no_local and pk.origin == cl.id:
            return pk  # [MQTT-3.8.3-3]

        if _LOOP_PLANE.active:
            w = _LOOP_PLANE.witness
            if w is not None:
                eff = pk.fixed_header.qos
                if eff > sub.qos:
                    eff = sub.qos
                if eff > 0 or cl.properties.props.topic_alias_maximum > 0:
                    # this delivery mutates loop-affine per-client state
                    # (packet ids / inflight / outbound aliases): the
                    # _deliver_to_client contract marshals it here
                    w.check_owner(
                        "client_state", "owner_touch", cl.net.loop,
                        detail=cl.id,
                    )
        topic = pk.topic_name
        if topic[:1] == NS_CHAR:
            topic = ns_local(topic)

        out = pk.copy(False)
        out.topic_name = topic
        if not self.hooks.on_acl_check(cl, topic, False):
            raise ERR_NOT_AUTHORIZED()
        if not sub.fwd_retained_flag and (
            (cl.properties.protocol_version == 5 and not sub.retain_as_published)
            or cl.properties.protocol_version < 5
        ):  # ![MQTT-3.3.1-13] [v3 MQTT-3.3.1-9]
            out.fixed_header.retain = False  # [MQTT-3.3.1-12]

        if sub.identifiers:  # [MQTT-3.3.4-3]
            out.properties.subscription_identifier = sorted(
                sub.identifiers.values()
            )  # [MQTT-3.3.4-4] ![MQTT-3.3.4-5]

        if out.fixed_header.qos > sub.qos:
            out.fixed_header.qos = sub.qos
        if out.fixed_header.qos > self.options.capabilities.maximum_qos:
            out.fixed_header.qos = self.options.capabilities.maximum_qos  # [MQTT-3.2.2-9]

        if cl.properties.props.topic_alias_maximum > 0:
            alias, alias_exists = cl.state.topic_aliases.outbound.set(topic)
            out.properties.topic_alias = alias
            if alias > 0:
                out.properties.topic_alias_flag = True
                if alias_exists:
                    out.topic_name = ""

        if out.fixed_header.qos > 0:
            # the inflight cap, a packet id [MQTT-4.3.2-1] [MQTT-4.3.3-1],
            # the store and the send quota under one acquisition, as the
            # encode-once fan-out takes them (_flush_variant)
            caps = self.options.capabilities
            st = cl.state
            # a slice's run of the socket takes its ids first
            pid = cl.settled_inflight().begin(
                st, out, caps.maximum_inflight, caps.maximum_packet_id
            )
            if pid < BEGIN_PARKED:
                self._qos_refused(cl, pk, pid)
                raise ERR_QUOTA_EXCEEDED()
            self.info.inflight += 1
            self.hooks.on_qos_publish(cl, out, out.created, 0)
            if pid == BEGIN_PARKED:
                out.expiry = -1  # mark for immediate resend once quota frees
                return out

        if cl.net.writer is None or cl.closed:
            raise CODE_DISCONNECT()

        cl._end_slice()  # frames wait in the queue now: not ready
        try:
            cl.state.outbound.put_nowait(out)
            cl.state.outbound_full_since = None
            self._stamp_outbound(cl)
        except asyncio.QueueFull:
            if cl.state.outbound_full_since is None:
                # slow-consumer eviction clock (overload SHED posture)
                cl.state.outbound_full_since = time.monotonic()
            self.info.messages_dropped += 1
            self.hooks.on_publish_dropped(cl, pk)
            if out.fixed_header.qos > 0:
                cl.state.inflight.delete(out.packet_id)  # rollback inflight
                cl.state.inflight.increase_send_quota()
            raise ERR_PENDING_CLIENT_WRITES_EXCEEDED() from None

        return out

    def publish_retained_to_client(self, cl: Client, sub: Subscription, existed: bool) -> None:
        """Send matching retained messages after a subscribe
        (server.go:1115-1133)."""
        if is_shared_filter(sub.filter):
            return  # 4.8.2 Non-normative: no retained on shared subscribe
        if (sub.retain_handling == 1 and existed) or sub.retain_handling == 2:
            return  # [MQTT-3.3.1-10] [MQTT-3.3.1-11]
        # value-copy: the reference ranges over Subscription values, so the
        # trie-stored subscription never carries fwd_retained_flag
        sub = replace(sub, fwd_retained_flag=True)
        # device-resident retained matching (ISSUE 16): the flat publish
        # kernel run in reverse answers wildcard filters against the
        # retained corpus; None (non-wildcard, $SHARE, fallback class,
        # open breaker) = host trie walk, the differential oracle
        retained_msgs: list = []
        if self._retained_engine is not None:
            names = self._retained_engine.match(sub.filter)
            if names is not None:
                retained_msgs = [
                    m
                    for m in (self.topics.retained.get(n) for n in names)
                    if m is not None
                ]
            else:
                retained_msgs = self.topics.messages(sub.filter)
        else:
            retained_msgs = self.topics.messages(sub.filter)
        for pkv in retained_msgs:  # [MQTT-3.8.4-4]
            # MQTT+ predicates apply to retained payloads too: the
            # sub.filter here is already the BASE filter, so the walk is
            # unchanged and only the delivery gate consults the rules
            if self._predicates is not None and not self._predicates.passes_retained(
                sub, bytes(pkv.payload)
            ):
                continue
            if (
                self._recrypt is not None
                and pkv.topic_name[:1] == NS_CHAR
            ):
                # an encrypted-namespace retained message is stored as
                # the PUBLISHER's ciphertext; deliver it re-keyed to
                # this subscriber (or not at all — mqtt_tpu.tenancy)
                pkv2 = self._recrypt_retained(cl, pkv)
                if pkv2 is None:
                    continue
                pkv = pkv2
            try:
                self.publish_to_client(cl, sub, pkv)
            except Exception as e:
                self.log.debug(
                    "failed to publish retained message: error=%s client=%s", e, cl.id
                )
                continue
            self.hooks.on_retain_published(cl, pkv)

    def _recrypt_retained(self, cl: Client, pkv: Packet) -> Optional[Packet]:
        """Re-key one retained encrypted-namespace message for a fresh
        subscriber (mqtt_tpu.tenancy): the store holds the publisher's
        ciphertext, the wire carries this subscriber's. None = withhold
        (keyless publisher or subscriber, malformed framing — counted by
        the engine). Scoped-but-unencrypted topics pass through."""
        tenant = (
            self._tenancy.tenant_of_topic(pkv.topic_name)
            if self._tenancy is not None
            else None
        )
        if tenant is None or not tenant.is_encrypted(ns_local(pkv.topic_name)):
            return pkv
        renc = self._recrypt
        plaintext = renc.open_publish(
            tenant, self._origin_idents(pkv), bytes(pkv.payload)
        )
        if plaintext is None:
            return None
        sealed = renc.seal_fanout(
            tenant, plaintext, [(cl.id, self._key_idents(cl.id, cl))]
        )
        data = sealed.get(cl.id)
        if data is None:
            return None
        out = pkv.copy(False)
        out.payload = data
        return out

    # -- live tenant re-key (ISSUE 20, the MQT-TZ rotation residual) -------

    def _publish_rekey_notice(
        self, tenant: str, state: str, epoch: int, extra: Optional[dict] = None
    ) -> None:
        """The $SYS half of the epoch protocol: a retained
        ``$SYS/broker/tenant/rekey`` message in the tenant's OWN
        namespace (its clients subscribe there to learn the new epoch)
        plus the global operator mirror, published on every state edge
        (distributing -> active -> retired)."""
        payload = {"tenant": tenant, "epoch": epoch, "state": state}
        if extra:
            payload.update(extra)
        data = json.dumps(payload).encode()
        now = int(time.time())  # brokerlint: ok=R3 $SYS rekey notice stamps are wall-clock (operator-correlatable)
        for topic in (
            ns_scope_topic(tenant, SYS_PREFIX + "/broker/tenant/rekey"),
            SYS_PREFIX + f"/broker/tenants/{tenant}/rekey",
        ):
            pk = Packet(
                fixed_header=FixedHeader(type=pkts.PUBLISH, retain=True),
                topic_name=topic,
                payload=data,
                created=now,
            )
            self.topics.retain_message(pk.copy(False))
            if self._retained_engine is not None:
                self._retained_engine.note_retained(topic, True)
            self.publish_to_subscribers(pk)

    def rekey_tenant(
        self, name: str, new_keys: dict, reseal_retained: bool = True
    ) -> dict:
        """Rotate a tenant's encryption keys LIVE (ISSUE 20): stage the
        next epoch's keys (``ident -> raw 16-byte key``), announce the
        distributing epoch on ``$SYS/broker/tenant/rekey``, re-seal the
        tenant's retained encrypted payloads across the rotation in
        batched device dispatches, then activate — new fan-out ticks
        seal under the new generation while in-flight ticks drain on
        their old-table snapshots. The OLD epoch stays decryptable
        (epoch-tagged nonces) until :meth:`retire_tenant_epoch`.

        Returns ``{"epoch", "old_epoch", "resealed"}``; raises
        ValueError when tenancy/recrypt is off or the tenant is
        unknown."""
        if self._tenancy is None or self._recrypt is None:
            raise ValueError("rekey requires tenancy + recrypt enabled")
        t = self._tenancy.get(name)
        if t is None:
            raise ValueError(f"unknown tenant {name!r}")
        renc = self._recrypt
        keys = self._tenancy.keys
        old_epoch = keys.current_epoch(name)
        epoch = keys.stage_epoch(name, new_keys)
        self._publish_rekey_notice(name, "distributing", epoch)
        resealed = 0
        if reseal_retained:
            resealed = self._reseal_tenant_retained(t, epoch)
        keys.activate_epoch(name)
        renc.note_rekey(name)
        self._publish_rekey_notice(
            name, "active", epoch, {"resealed": resealed}
        )
        self.log.info(
            "tenant %s re-keyed: epoch %d -> %d, %d retained re-sealed",
            name, old_epoch, epoch, resealed,
        )
        return {"epoch": epoch, "old_epoch": old_epoch, "resealed": resealed}

    def retire_tenant_epoch(self, name: str, epoch: int) -> int:
        """Retire a drained epoch: tagged publishes under it now drop
        (counted as stale), its round-key rows are scrubbed, and the
        retirement is announced on the rekey $SYS topic. Returns how
        many key rows were scrubbed."""
        if self._tenancy is None:
            raise ValueError("rekey requires tenancy enabled")
        scrubbed = self._tenancy.keys.retire_epoch(name, epoch)
        self._publish_rekey_notice(
            name, "retired", epoch, {"scrubbed": scrubbed}
        )
        return scrubbed

    def _reseal_tenant_retained(self, t, epoch: int) -> int:
        """Re-seal every retained encrypted-namespace payload of one
        tenant from its CURRENT generation to the staged ``epoch`` in
        ONE batched keystream dispatch (decrypt + seal blocks share the
        call — tenancy.RecryptEngine.reseal_batch). The rewritten
        payloads ride retain_message, so durable persistence and the
        retained-match engine see the new ciphertext."""
        renc = self._recrypt
        keys = self._tenancy.keys
        prefix = NS_CHAR + t.name + "/"
        victims: list = []
        items: list = []
        for topic, pkv in self.topics.retained.get_all().items():
            if not topic.startswith(prefix) or not pkv.payload:
                continue
            local = ns_local(topic)
            if local.startswith("$SYS") or not t.is_encrypted(local):
                continue
            idents = self._origin_idents(pkv)
            old_kid = new_kid = -1
            for ident in idents:
                if not ident:
                    continue
                old_kid = keys.key_id(t.name, ident)
                new_kid = keys.kid_for_epoch(t.name, ident, epoch)
                if old_kid >= 0 and new_kid >= 0:
                    break
            victims.append((topic, pkv))
            items.append((bytes(pkv.payload), old_kid, new_kid))
        if not items:
            return 0
        resealed = renc.reseal_batch(t, items, epoch)
        n = 0
        for (topic, pkv), data in zip(victims, resealed):
            if data is None:
                continue  # keyless origin: the old ciphertext stands
            out = pkv.copy(False)
            out.payload = data
            out.fixed_header.retain = True
            self.retain_message(self.clients.get(out.origin), out)
            n += 1
        return n

    def build_ack(
        self, packet_id: int, pkt: int, qos: int, properties: Properties, reason: Code
    ) -> Packet:
        """A standardized ack for puback/pubrec/pubrel/pubcomp
        (server.go:1136-1157)."""
        if self.options.capabilities.compatibilities.no_inherited_properties_on_ack:
            properties = Properties()
        else:
            # by value, as the reference passes them: what the ack sets
            # here and at write time (the reason string, its own expiry
            # interval) never lands on the publish it answers
            properties = properties.value()
        if reason.code >= ERR_UNSPECIFIED_ERROR.code:
            properties.reason_string = reason.reason
        now = int(time.time())  # brokerlint: ok=R3 ack created/expiry stamps are wall-clock (message-expiry contract)
        return Packet(
            fixed_header=FixedHeader(type=pkt, qos=qos),
            packet_id=packet_id,  # [MQTT-2.2.1-5]
            reason_code=reason.code,  # [MQTT-3.4.2-1]
            properties=properties,
            created=now,
            expiry=now + self.options.capabilities.maximum_message_expiry_interval,
        )

    # -- qos acks ----------------------------------------------------------

    def process_puback(self, cl: Client, pk: Packet) -> None:
        """(server.go:1160-1172)"""
        if cl.state.inflight.get(pk.packet_id) is None:
            return  # omit ErrPacketIdentifierNotFound
        if cl.state.inflight.delete(pk.packet_id):  # [MQTT-4.3.2-5]
            cl.state.inflight.increase_send_quota()
            self.info.inflight -= 1
            self.hooks.on_qos_complete(cl, pk)

    def process_pubrec(self, cl: Client, pk: Packet) -> None:
        """(server.go:1175-1192)"""
        if cl.state.inflight.get(pk.packet_id) is None:  # [MQTT-4.3.3-7/-13]
            cl.write_packet(
                self.build_ack(
                    pk.packet_id, pkts.PUBREL, 1, pk.properties, ERR_PACKET_IDENTIFIER_NOT_FOUND
                )
            )
            return
        if pk.reason_code >= ERR_UNSPECIFIED_ERROR.code or not pk.reason_code_valid():
            if cl.state.inflight.delete(pk.packet_id):
                self.info.inflight -= 1
            self.hooks.on_qos_dropped(cl, pk)
            return  # MQTT5 section 4.13.2 paragraph 2
        ack = self.build_ack(pk.packet_id, pkts.PUBREL, 1, pk.properties, CODE_SUCCESS)
        cl.state.inflight.decrease_receive_quota()
        cl.state.inflight.set(ack)  # [MQTT-4.3.3-5]
        # persist the PUBLISH -> PUBREL window transition (ISSUE 20):
        # the durable record must flip with the in-memory window, or a
        # crash-restore re-inflates the window as an unacked PUBLISH and
        # re-delivers a message the receiver already PUBREC'd — the
        # exactly-once violation the qos2_fanout scenario's kill -9 leg
        # caught ([MQTT-4.3.3-6]: no PUBLISH re-send once PUBREC is in)
        self.hooks.on_qos_publish(cl, ack, ack.created, 0)
        cl.write_packet(ack)

    def process_pubrel(self, cl: Client, pk: Packet) -> None:
        """(server.go:1195-1224)"""
        if cl.state.inflight.get(pk.packet_id) is None:  # [MQTT-4.3.3-7/-13]
            cl.write_packet(
                self.build_ack(
                    pk.packet_id, pkts.PUBCOMP, 0, pk.properties, ERR_PACKET_IDENTIFIER_NOT_FOUND
                )
            )
            return
        if pk.reason_code >= ERR_UNSPECIFIED_ERROR.code or not pk.reason_code_valid():
            if cl.state.inflight.delete(pk.packet_id):
                self.info.inflight -= 1
            self.hooks.on_qos_dropped(cl, pk)
            return
        ack = self.build_ack(pk.packet_id, pkts.PUBCOMP, 0, pk.properties, CODE_SUCCESS)
        cl.state.inflight.set(ack)
        cl.write_packet(ack)
        cl.state.inflight.increase_receive_quota()
        cl.state.inflight.increase_send_quota()
        if cl.state.inflight.delete(pk.packet_id):  # [MQTT-4.3.3-12]
            self.info.inflight -= 1
            self.hooks.on_qos_complete(cl, pk)

    def process_pubcomp(self, cl: Client, pk: Packet) -> None:
        """(server.go:1227-1237)"""
        cl.state.inflight.increase_receive_quota()
        cl.state.inflight.increase_send_quota()
        if cl.state.inflight.delete(pk.packet_id):
            self.info.inflight -= 1
            self.hooks.on_qos_complete(cl, pk)

    # -- subscribe / unsubscribe -------------------------------------------

    def process_subscribe(self, cl: Client, pk: Packet) -> None:
        """(server.go:1240-1312)"""
        pk = self.hooks.on_subscribe(cl, pk)
        code = CODE_SUCCESS
        if cl.state.inflight.get(pk.packet_id) is not None:
            code = ERR_PACKET_IDENTIFIER_IN_USE

        caps = self.options.capabilities
        filter_existed = [False] * len(pk.filters)
        reason_codes = bytearray(len(pk.filters))
        for i, sub in enumerate(pk.filters):
            if code != CODE_SUCCESS:
                reason_codes[i] = code.code  # NB 3.9.3 Non-normative 0x91
                continue
            # MQTT+ predicate suffix (mqtt_tpu.predicates): split BEFORE
            # validation so the SUBACK reason, the ACL check, $SHARE
            # parsing, and the trie all see the BASE filter — the suffix
            # never leaks past this point. Registration waits for the
            # success branch so a refused filter leaks no rule.
            pred_suffix = ""
            if self._predicates is not None:
                base, pred_suffix = split_predicate_suffix(sub.filter)
                if pred_suffix:
                    sub.filter = base
            if not is_valid_filter(sub.filter, False):
                reason_codes[i] = ERR_TOPIC_FILTER_INVALID.code
            elif sub.no_local and is_shared_filter(sub.filter):
                reason_codes[i] = ERR_PROTOCOL_VIOLATION_INVALID_SHARED_NO_LOCAL.code  # [MQTT-3.8.3-4]
            elif not self.hooks.on_acl_check(cl, sub.filter, False):
                reason_codes[i] = ERR_NOT_AUTHORIZED.code
                if caps.compatibilities.obscure_not_authorized:
                    reason_codes[i] = ERR_UNSPECIFIED_ERROR.code
            elif self._subscribe_quota_refused(cl, sub):
                # tenant subscription COUNT cap (ISSUE 16): 0x97 before
                # any rule/trie registration (the v3 clamp below turns
                # it into 0x80 for pre-v5 clients)
                reason_codes[i] = ERR_QUOTA_EXCEEDED.code
            else:
                if cl.tenant is not None:
                    # tenant namespace (mqtt_tpu.tenancy): validation,
                    # $SHARE parsing, and the ACL all saw the LOCAL
                    # filter above; everything stored or matched from
                    # here — trie, client state, retained walk,
                    # persistence, cluster presence — carries the
                    # scoped key, so two tenants' identical filter
                    # strings live on disjoint subtrees
                    sub.filter = ns_scope_filter(cl.tenant.name, sub.filter)
                if pred_suffix:
                    self._predicates.register(pred_suffix)
                    sub.predicates = (pred_suffix,)
                if self._predicates is not None:
                    # [MQTT-3.8.4-3] a re-subscribe REPLACES the stored
                    # subscription: drop the replaced one's rule refs
                    # (after registering, so a same-suffix replace never
                    # drops the rule to zero in between)
                    old = cl.state.subscriptions.get(sub.filter)
                    if old is not None and old.predicates:
                        self._predicates.release(old.predicates)
                is_new = self.topics.subscribe(cl.id, sub)  # [MQTT-3.8.4-3]
                if is_new:
                    self.info.subscriptions += 1
                    if cl.tenant is not None and sub.filter[:1] == NS_CHAR:
                        cl.tenant.subscriptions_count += 1
                cl.state.subscriptions.add(sub.filter, sub)  # [MQTT-3.2.2-10]
                # granted qos caps at server max [MQTT-3.2.2-9] without
                # mutating the trie-stored subscription (the reference caps a
                # value copy, server.go:1269-1274)
                filter_existed[i] = not is_new
                reason_codes[i] = min(sub.qos, caps.maximum_qos)  # [MQTT-3.9.3-1]

            if reason_codes[i] > 2 and cl.properties.protocol_version < 5:  # MQTT3
                reason_codes[i] = ERR_UNSPECIFIED_ERROR.code

        ack = Packet(  # [MQTT-3.8.4-1] [MQTT-3.8.4-5]
            fixed_header=FixedHeader(type=pkts.SUBACK),
            packet_id=pk.packet_id,  # [MQTT-2.2.1-6] [MQTT-3.8.4-2]
            reason_codes=bytes(reason_codes),  # [MQTT-3.8.4-6]
            properties=Properties(user=pk.properties.user),
        )
        if code.code >= ERR_UNSPECIFIED_ERROR.code:
            ack.properties.reason_string = code.reason

        self.hooks.on_subscribed(cl, pk, bytes(reason_codes))
        cl.write_packet(ack)

        for i, sub in enumerate(pk.filters):  # [MQTT-3.3.1-9]
            if reason_codes[i] >= ERR_UNSPECIFIED_ERROR.code:
                continue
            self.publish_retained_to_client(cl, sub, filter_existed[i])

    def process_unsubscribe(self, cl: Client, pk: Packet) -> None:
        """(server.go:1315-1356)"""
        code = CODE_SUCCESS
        if cl.state.inflight.get(pk.packet_id) is not None:
            code = ERR_PACKET_IDENTIFIER_IN_USE
        pk = self.hooks.on_unsubscribe(cl, pk)
        reason_codes = bytearray(len(pk.filters))
        for i, sub in enumerate(pk.filters):  # [MQTT-3.10.4-6] [MQTT-3.11.3-1]
            if code != CODE_SUCCESS:
                reason_codes[i] = code.code
                continue
            if self._predicates is not None:
                # an UNSUBSCRIBE naming the original predicated filter
                # must remove the subscription stored under its base
                base, pred_suffix = split_predicate_suffix(sub.filter)
                if pred_suffix:
                    sub.filter = base
            if cl.tenant is not None:
                # the stored key is namespace-scoped (process_subscribe)
                sub.filter = ns_scope_filter(cl.tenant.name, sub.filter)
            if self._predicates is not None:
                old = cl.state.subscriptions.get(sub.filter)
                if old is not None and old.predicates:
                    self._predicates.release(old.predicates)
            if self.topics.unsubscribe(sub.filter, cl.id):
                self.info.subscriptions -= 1
                if (
                    cl.tenant is not None
                    and sub.filter[:1] == NS_CHAR
                    and cl.tenant.subscriptions_count > 0
                ):
                    cl.tenant.subscriptions_count -= 1
                reason_codes[i] = CODE_SUCCESS.code
            else:
                reason_codes[i] = pkts.CODE_NO_SUBSCRIPTION_EXISTED.code
            cl.state.subscriptions.delete(sub.filter)  # [MQTT-3.10.4-2]

        ack = Packet(  # [MQTT-3.10.4-4]
            fixed_header=FixedHeader(type=pkts.UNSUBACK),
            packet_id=pk.packet_id,  # [MQTT-2.2.1-6] [MQTT-3.10.4-5]
            reason_codes=bytes(reason_codes),  # [MQTT-3.11.3-2]
            properties=Properties(user=pk.properties.user),
        )
        if code.code >= ERR_UNSPECIFIED_ERROR.code:
            ack.properties.reason_string = code.reason

        self.hooks.on_unsubscribed(cl, pk)
        cl.write_packet(ack)

    def unsubscribe_client(self, cl: Client) -> None:
        """Remove all of a client's subscriptions (server.go:1359-1379)."""
        filter_map = cl.state.subscriptions.get_all()
        for k in filter_map:
            cl.state.subscriptions.delete(k)
        if cl.is_taken_over:
            return  # the inheriting session keeps the rules referenced
        for k, sub in filter_map.items():
            if self._predicates is not None and sub.predicates:
                self._predicates.release(sub.predicates)
            if self.topics.unsubscribe(k, cl.id):
                self.info.subscriptions -= 1
                if self._tenancy is not None and k[:1] == NS_CHAR:
                    # restored clients may not carry cl.tenant — resolve
                    # the owner off the scoped filter itself
                    t = self._tenancy.tenant_of_topic(k)
                    if t is not None and t.subscriptions_count > 0:
                        t.subscriptions_count -= 1
        self.hooks.on_unsubscribed(
            cl,
            Packet(
                fixed_header=FixedHeader(type=pkts.UNSUBSCRIBE),
                filters=list(filter_map.values()),
            ),
        )

    # -- auth / disconnect -------------------------------------------------

    def process_auth(self, cl: Client, pk: Packet) -> None:
        """(server.go:1382-1389)"""
        self.hooks.on_auth_packet(cl, pk)

    def process_disconnect(self, cl: Client, pk: Packet) -> None:
        """(server.go:1392-1410)"""
        if pk.properties.session_expiry_interval_flag:
            if (
                pk.properties.session_expiry_interval > 0
                and cl.properties.props.session_expiry_interval == 0
            ):
                raise ERR_PROTOCOL_VIOLATION_ZERO_NON_ZERO_EXPIRY()
            cl.properties.props.session_expiry_interval = pk.properties.session_expiry_interval
            cl.properties.props.session_expiry_interval_flag = True

        if pk.reason_code == CODE_DISCONNECT_WILL_MESSAGE.code:  # [MQTT-3.1.2.5]
            raise CODE_DISCONNECT_WILL_MESSAGE()

        self.will_delayed.delete(cl.id)  # [MQTT-3.1.3-9] [MQTT-3.1.2-8]
        # discard the will STRUCT too, not just a pending delayed entry
        # [MQTT-3.14.4-3] (ISSUE 20 will fixes): the read loop usually
        # returns cleanly after stop() and clears it, but a transport
        # already racing its own teardown can surface the close as a
        # ConnectionError first — and that path fires send_lwt
        cl.properties.will = Will()
        cl.stop(CODE_DISCONNECT())  # [MQTT-3.14.4-2]

    def disconnect_client(self, cl: Client, code: Code) -> None:
        """Send DISCONNECT and close (server.go:1413-1437). Raises the code
        for error-class disconnects (mirrors the reference's error return).

        Under the shard fabric a disconnect targeting a client ANOTHER
        shard owns (cross-shard takeover, the main loop's eviction/drain
        paths) is marshaled onto the owning loop — the DISCONNECT write
        and the transport close are loop-affine. The marshaled form
        cannot raise; its callers already treat the raise as advisory
        (every call site catches Code)."""
        if self._fabric is not None and not self._client_loop_local(cl):
            loop = cl.net.loop
            if loop is not None and loop.is_running():
                try:
                    loop.call_soon_threadsafe(
                        self._disconnect_client_remote, cl, code
                    )
                    return
                except RuntimeError:
                    pass  # owner loop gone; close directly below
        out = Packet(
            fixed_header=FixedHeader(type=pkts.DISCONNECT),
            reason_code=code.code,
            properties=Properties(),
        )
        if code.code >= ERR_UNSPECIFIED_ERROR.code:
            out.properties.reason_string = code.reason  # [MQTT-3.14.2-1]
        try:
            cl.write_packet(out)
        except Exception:  # brokerlint: ok=R4 we're already disconnecting; write errors don't matter
            pass
        if not self.options.capabilities.compatibilities.passive_client_disconnect:
            cl.stop(code)
            if code.code >= ERR_UNSPECIFIED_ERROR.code:
                raise code()

    def _disconnect_client_remote(self, cl: Client, code: Code) -> None:
        """The owner-shard half of a marshaled disconnect."""
        try:
            self.disconnect_client(cl, code)
        except Code:
            pass

    # -- $SYS / housekeeping -----------------------------------------------

    def publish_sys_topics(self) -> None:
        """Publish retained $SYS values (server.go:1442-1492)."""
        now = int(time.time())  # brokerlint: ok=R3 $SYS/broker/time is wall-clock by definition
        self.info.memory_alloc = rss_bytes()
        self.info.threads = threading.active_count()
        self.info.time = now
        # monotonic anchor, not `now - started`: a wall-clock step (NTP,
        # suspend) must not bend $SYS/broker/uptime (system.Info)
        self.info.uptime = self.info.uptime_now()
        self.info.clients_total = len(self.clients)
        self.info.clients_disconnected = self.info.clients_total - self.info.clients_connected

        info = self.info.clone()
        topics = {
            SYS_PREFIX + "/broker/version": info.version,
            SYS_PREFIX + "/broker/time": str(info.time),
            SYS_PREFIX + "/broker/uptime": str(info.uptime),
            SYS_PREFIX + "/broker/started": str(info.started),
            SYS_PREFIX + "/broker/load/bytes/received": str(info.bytes_received),
            SYS_PREFIX + "/broker/load/bytes/sent": str(info.bytes_sent),
            SYS_PREFIX + "/broker/clients/connected": str(info.clients_connected),
            SYS_PREFIX + "/broker/clients/disconnected": str(info.clients_disconnected),
            SYS_PREFIX + "/broker/clients/maximum": str(info.clients_maximum),
            SYS_PREFIX + "/broker/clients/total": str(info.clients_total),
            SYS_PREFIX + "/broker/packets/received": str(info.packets_received),
            SYS_PREFIX + "/broker/packets/sent": str(info.packets_sent),
            SYS_PREFIX + "/broker/messages/received": str(info.messages_received),
            SYS_PREFIX + "/broker/messages/sent": str(info.messages_sent),
            SYS_PREFIX + "/broker/messages/dropped": str(info.messages_dropped),
            SYS_PREFIX + "/broker/messages/inflight": str(info.inflight),
            SYS_PREFIX + "/broker/retained": str(info.retained),
            SYS_PREFIX + "/broker/subscriptions": str(info.subscriptions),
            SYS_PREFIX + "/broker/system/memory": str(info.memory_alloc),
            SYS_PREFIX + "/broker/system/threads": str(info.threads),
            # what the trie costs (TopicsIndex's three counts): nodes,
            # containers across them, subscriptions held
            SYS_PREFIX + "/broker/topics/particles": str(self.topics.particles),
            SYS_PREFIX + "/broker/topics/particle_maps": str(
                self.topics.particle_maps
            ),
            SYS_PREFIX + "/broker/topics/held": str(self.topics.held),
        }
        for c in _OPS_COUNTERS:
            if c.sys_path is not None:
                topics[SYS_PREFIX + c.sys_path] = str(getattr(self._ops, c.attr))
        # the lazy results forced into dicts
        topics[SYS_PREFIX + "/broker/fanout/view_materializations"] = str(
            self._view_materializations()
        )
        if self.matcher is not None:
            # device-matcher observability (MatcherStats.as_dict): batches,
            # topics, host_fallbacks, overflows, rebuilds, fallback_ratio
            for key, val in self.matcher.stats.as_dict().items():
                topics[SYS_PREFIX + "/broker/matcher/" + key] = str(val)
            gauges = getattr(self.matcher, "breaker_gauges", None)
            if callable(gauges):
                # degradation-manager observability (mqtt_tpu.resilience):
                # breaker state/trips, fallback rates, probe counters
                for key, val in gauges().items():
                    topics[
                        SYS_PREFIX + "/broker/matcher/breaker/" + key
                    ] = str(val)
        if self._predicates is not None:
            # MQTT+ predicate plane (mqtt_tpu.predicates): rule counts,
            # device vs host eval split, filter selectivity, aggregation
            # emissions, oracle verdicts, breaker posture
            for key, val in self._predicates.gauges().items():
                topics[SYS_PREFIX + "/broker/predicates/" + key] = str(val)
        if self._recrypt is not None:
            # re-encryption observability (mqtt_tpu.tenancy): batch/block
            # split, oracle verdicts, key count, breaker posture
            for key, val in self._recrypt.gauges().items():
                topics[SYS_PREFIX + "/broker/recrypt/" + key] = str(val)
        if self._tenancy is not None:
            # per-tenant $SYS scoping: each ACTIVE tenant's counters
            # publish INTO its own namespace (a tenant subscribing
            # $SYS/broker/tenant/# sees only its own broker stats —
            # structurally, like everything else) plus a global
            # operator mirror under $SYS/broker/tenants/<name>/
            for t in self._tenancy.active_tenants():
                for key, val in t.sys_rows().items():
                    topics[
                        ns_scope_topic(
                            t.name, SYS_PREFIX + "/broker/tenant/" + key
                        )
                    ] = str(val)
                    topics[
                        SYS_PREFIX + f"/broker/tenants/{t.name}/" + key
                    ] = str(val)
        if self.overload is not None:
            # overload-governor observability (mqtt_tpu.overload): state,
            # transition/shed/eviction/throttle counters, per-signal
            # pressures (signal/*) and their high-water marks (peak/*)
            for key, val in self.overload.gauges().items():
                topics[SYS_PREFIX + "/broker/overload/" + key] = str(val)
            topics[SYS_PREFIX + "/broker/overload/outbound_backlog"] = str(
                self._outbound_backlog
            )
            if self._stage is not None:
                st = self._stage
                topics[SYS_PREFIX + "/broker/overload/stage_pending"] = str(
                    st.pending_depth
                )
                topics[
                    SYS_PREFIX + "/broker/overload/stage_peak_pending"
                ] = str(st.peak_pending)
                topics[
                    SYS_PREFIX + "/broker/overload/stage_admission_fallbacks"
                ] = str(st.admission_fallbacks)
                topics[
                    SYS_PREFIX + "/broker/overload/stage_order_held"
                ] = str(st.order_held)
        if self.telemetry is not None:
            # telemetry-plane observability (mqtt_tpu.telemetry): stage
            # histogram percentiles, batch occupancy, fallback classes,
            # queue-wait, flight-recorder state
            for key, val in self.telemetry.sys_tree().items():
                topics[SYS_PREFIX + "/broker/telemetry/" + key] = str(val)
        if self.device_stats is not None:
            # per-device observability (ISSUE 18, ops/devicestats): HBM,
            # duty cycles, skew, and the compile ledger as retained rows
            for key, val in self.device_stats.sys_tree().items():
                topics[SYS_PREFIX + "/broker/devices/" + key] = str(val)
        if self._cluster is not None:
            # worker-mesh observability (mqtt_tpu.cluster)
            c = self._cluster
            topics[SYS_PREFIX + "/broker/cluster/worker"] = str(c.worker_id)
            topics[SYS_PREFIX + "/broker/cluster/peers"] = str(c.peer_count)
            topics[SYS_PREFIX + "/broker/cluster/dropped_forwards"] = str(
                c.dropped_forwards
            )
            # backpressure + link-health gauges (mqtt_tpu.cluster known
            # limits: QoS>0 forwards DROP at the peer-buffer cap — the
            # drop is counted here, never silent)
            topics[SYS_PREFIX + "/broker/cluster/dropped_qos_forwards"] = str(
                c.dropped_qos_forwards
            )
            topics[SYS_PREFIX + "/broker/cluster/reconnects"] = str(
                c.reconnects_total
            )
            # overload tier: QoS0 forwards shed at the governor's reduced
            # peer-buffer cap (subset of dropped_forwards, never silent)
            topics[SYS_PREFIX + "/broker/cluster/shed_qos0_forwards"] = str(
                c.shed_qos0_forwards
            )
            # partition-tolerance gauges (ISSUE 5): the drop-class split
            # (partition-time vs backlog), the park buffer, and replays
            topics[SYS_PREFIX + "/broker/cluster/peer_drops_partition"] = str(
                c.dropped_partition
            )
            topics[SYS_PREFIX + "/broker/cluster/peer_drops_backlog"] = str(
                c.dropped_backlog
            )
            topics[SYS_PREFIX + "/broker/cluster/parked_forwards"] = str(
                c.parked_forwards
            )
            topics[SYS_PREFIX + "/broker/cluster/replayed_forwards"] = str(
                c.replayed_forwards
            )
            # control-plane byte volume (the drill's O(degree) gossip
            # assertion reads it per worker)
            topics[SYS_PREFIX + "/broker/cluster/control_bytes"] = str(
                c.control_bytes
            )
            for peer, n in sorted(c.dropped_by_peer.items()):
                topics[
                    SYS_PREFIX + f"/broker/cluster/peer/{peer}/dropped_forwards"
                ] = str(n)
            for peer, ph in sorted(c._health.items()):
                topics[
                    SYS_PREFIX + f"/broker/cluster/peer/{peer}/health"
                ] = ph.state
            if c.topo is not None:
                # spanning-tree gauges (ISSUE 9): epoch, live edge
                # count, the loop/duplicate guards, and the summary
                # routing split — everything the partition-storm drill
                # asserts from the outside
                t = c.topo
                topics[SYS_PREFIX + "/broker/cluster/tree/epoch"] = str(
                    t.epoch_num()
                )
                topics[SYS_PREFIX + "/broker/cluster/tree/neighbors"] = str(
                    len(t.neighbors())
                )
                topics[SYS_PREFIX + "/broker/cluster/tree/links"] = str(
                    sum(1 for p in t.neighbors() if p in c._writers)
                )
                topics[SYS_PREFIX + "/broker/cluster/tree/re_elections"] = str(
                    t.re_elections
                )
                topics[
                    SYS_PREFIX + "/broker/cluster/tree/duplicates_suppressed"
                ] = str(c.duplicates_suppressed)
                topics[
                    SYS_PREFIX + "/broker/cluster/tree/stale_epoch_frames"
                ] = str(c.stale_epoch_frames)
                topics[
                    SYS_PREFIX + "/broker/cluster/tree/summary_filtered"
                ] = str(c.summary_filtered_forwards)
                topics[
                    SYS_PREFIX + "/broker/cluster/tree/summary_passthrough"
                ] = str(c.summary_passthrough_forwards)
                # predicate push-down + root-failover gauges (ISSUE 17):
                # the WAN drill asserts both from the outside
                topics[
                    SYS_PREFIX + "/broker/cluster/tree/predicate_filtered"
                ] = str(c.summary_predicate_filtered_forwards)
                topics[
                    SYS_PREFIX + "/broker/cluster/tree/root_failovers"
                ] = str(c.root_failovers)
                topics[
                    SYS_PREFIX + "/broker/cluster/tree/root_failover_last_s"
                ] = "%.6f" % c.root_failover_last_s
                topics[SYS_PREFIX + "/broker/cluster/tree/root"] = str(
                    t.root()
                )
                topics[SYS_PREFIX + "/broker/cluster/tree/successor"] = str(
                    t.successor()
                )
        pk = Packet(
            fixed_header=FixedHeader(type=pkts.PUBLISH, retain=True),
            created=now,
        )
        for topic, payload in topics.items():
            pk.topic_name = topic
            pk.payload = payload.encode()
            self.topics.retain_message(pk.copy(False))
            if self._retained_engine is not None:
                self._retained_engine.note_retained(topic, True)
            self.publish_to_subscribers(pk)
        if (
            self._durable["recovering"]
            or self._durable["replayed_keys"]
            or self._durable["restore_batches"]
        ):
            # keep the recovery tree fresh on the $SYS cadence (only
            # once a durable restore has actually happened — brokers
            # with no storage hook never grow the subtree)
            self.publish_durable_sys()
        self.hooks.on_sys_info_tick(info)

    async def close(self) -> None:
        """Gracefully stop the server, listeners, clients, and hooks
        (server.go:1495-1504)."""
        self._draining = True  # late CONNECTs now refuse with 0x89
        self.done.set()
        self.log.info("gracefully stopping server")
        await self.listeners.close_all(self._close_listener_clients)
        if self._fabric is not None:
            # after the listeners: the drain disconnects were marshaled
            # onto the shard loops, which must still be alive to run
            # them; stop() then drains the establish tasks and joins
            # the shard threads (mqtt_tpu.shards)
            await self._fabric.stop()
            self._fabric = None
        # stage first (parked publishes resolve via the host walk), then
        # the matcher; shutdown LWT publishes and clean-session
        # unsubscribes must still flow through the live delta overlay
        if self._stage is not None:
            await self._stage.stop()
            self._stage = None
        if self._jax_trace_active:
            self._jax_trace_active = False
            try:
                import jax

                jax.profiler.stop_trace()
            except Exception:  # brokerlint: ok=R4 teardown; a failed profiler stop must not abort the drain
                self.log.exception("jax.profiler trace failed to stop")
            if self.profiler is not None:
                self.profiler.poll()  # the session ended: freeze its slice
        if self.profiler is not None:
            self.profiler.stop()
        if self.matcher is not None:
            self.matcher.close()
        if self._lock_plane_armed:
            self._lock_plane_armed = False
            self.telemetry.lock_plane.disarm()
        self.hooks.on_stopped()
        self.hooks.stop()
        if self._event_loop_task is not None:
            self._event_loop_task.cancel()
        self.log.info("mqtt_tpu server stopped")

    def _close_listener_clients(self, listener: str) -> None:
        """(server.go:1507-1512)"""
        for cl in self.clients.get_by_listener(listener):
            try:
                self.disconnect_client(cl, ERR_SERVER_SHUTTING_DOWN)
            except Code:
                pass

    def send_lwt(self, cl: Client) -> None:
        """Issue (or delay) a client's will message (server.go:1515-1551)."""
        if cl.properties.will.flag == 0:
            return
        if cl.is_taken_over:
            # session takeover is not an ungraceful disconnect: the
            # inheriting connection IS the client, so the old
            # connection's will must not fire (ISSUE 20 will fixes —
            # the read loop's teardown path lands here after
            # disconnect_client(ERR_SESSION_TAKEN_OVER) aborts it)
            cl.properties.will = Will()
            return
        if self.overload is not None and not self.overload.admit(cl):
            # wills ride the same shed accounting as live publishes
            # (ISSUE 20): a mass-disconnect will storm against a broker
            # already in SHED must not bypass the governor — the will is
            # dropped AND counted, exactly like an admitted-path shed
            self.info.messages_dropped += 1
            if cl.tenant is not None:
                cl.tenant.messages_dropped += 1
            cl.properties.will = Will()
            return
        modified = self.hooks.on_will(cl, cl.properties.will)
        now = int(time.time())  # brokerlint: ok=R3 will-message created/expiry stamps are wall-clock
        pk = Packet(
            fixed_header=FixedHeader(
                type=pkts.PUBLISH,
                retain=modified.retain,  # [MQTT-3.1.2-14/-15]
                qos=modified.qos,
            ),
            topic_name=modified.topic_name,
            payload=modified.payload,
            properties=Properties(user=modified.user),
            origin=cl.id,
            created=now,
        )
        if cl.tenant is not None:
            # a tenant's will fires into its own namespace — exactly
            # like its live publishes (mqtt_tpu.tenancy)
            pk.topic_name = ns_scope_topic(cl.tenant.name, pk.topic_name)
        if cl.properties.will.will_delay_interval > 0:
            pk.connect.will_properties.will_delay_interval = (
                cl.properties.will.will_delay_interval
            )
            pk.expiry = now + pk.connect.will_properties.will_delay_interval
            self.will_delayed.add(cl.id, pk)
            return
        if pk.fixed_header.retain:
            self.retain_message(cl, pk)
        self.publish_to_subscribers(pk)  # [MQTT-3.1.2-8]
        cl.properties.will.flag = 0  # [MQTT-3.1.2-10]
        self.hooks.on_will_sent(cl, pk)

    # -- persistence restore (server.go:1554-1692) -------------------------

    def read_store(self) -> None:
        # durable recovery window (ISSUE 16): healthz answers 503
        # `recovering` from the first restored byte until serve() has
        # the maps actually being served (after hooks.on_started()).
        # Restore failures propagate — serving a silently-partial
        # session map would be worse than refusing to start.
        self._durable["recovering"] = True
        t0 = time.perf_counter()
        try:
            if self.hooks.provides(STORED_CLIENTS):
                clients = self.hooks.stored_clients()
                self.load_clients(clients)
                self.log.debug("loaded clients from store: len=%d", len(clients))
            if self.hooks.provides(STORED_SUBSCRIPTIONS):
                subs = self.hooks.stored_subscriptions()
                self.load_subscriptions(subs)
                self.log.debug("loaded subscriptions from store: len=%d", len(subs))
            if self.hooks.provides(STORED_INFLIGHT_MESSAGES):
                inflight = self.hooks.stored_inflight_messages()
                self.load_inflight(inflight)
                self.log.debug("loaded inflights from store: len=%d", len(inflight))
            if self.hooks.provides(STORED_RETAINED_MESSAGES):
                retained = self.hooks.stored_retained_messages()
                self.load_retained(retained)
                self.log.debug("loaded retained messages from store: len=%d", len(retained))
            if self.hooks.provides(STORED_SYS_INFO):
                sys_info = self.hooks.stored_sys_info()
                if sys_info is not None:
                    self.load_server_info(sys_info.info)
                    self.log.debug("loaded $SYS info from store")
        finally:
            self._durable["recovery_seconds"] = time.perf_counter() - t0
            self._durable["replayed_keys"] = int(
                self._durable_store_stats().get("replayed_keys", 0)
            )

    def load_server_info(self, v: Info) -> None:
        if self.options.capabilities.compatibilities.restore_sys_info_on_restart:
            self.info.bytes_received = v.bytes_received
            self.info.bytes_sent = v.bytes_sent
            self.info.clients_maximum = v.clients_maximum
            self.info.clients_total = v.clients_total
            self.info.clients_disconnected = v.clients_disconnected
            self.info.messages_received = v.messages_received
            self.info.messages_sent = v.messages_sent
            self.info.messages_dropped = v.messages_dropped
            self.info.packets_received = v.packets_received
            self.info.packets_sent = v.packets_sent
            self.info.inflight_dropped = v.inflight_dropped
        self.info.retained = v.retained
        self.info.inflight = v.inflight
        self.info.subscriptions = v.subscriptions

    def load_subscriptions(self, v: list) -> None:
        entries: list[tuple[str, Subscription]] = []
        for sub in v:
            predicates = tuple(getattr(sub, "predicates", ()) or ())
            if predicates and self._predicates is not None:
                # re-intern persisted MQTT+ rules (a restart must keep
                # filtering; with the plane disabled the subscription
                # restores as its base filter and fails open)
                for suffix in predicates:
                    try:
                        self._predicates.register(suffix)
                    except ValueError:
                        predicates = ()
                        break
            sb = Subscription(
                filter=sub.filter,
                retain_handling=sub.retain_handling,
                qos=sub.qos,
                retain_as_published=sub.retain_as_published,
                no_local=sub.no_local,
                identifier=sub.identifier,
                predicates=predicates,
            )
            entries.append((sub.client, sb))
        # batched re-registration (ISSUE 16): a million-session restart
        # must not pay a trie lock round-trip per subscription — chunks
        # flow through the trie's bulk-insert path, as one bulk load of
        # the trie (the device matcher builds once, when it closes; a
        # caller that feeds this a stored batch at a time holds
        # `self.topics.bulk_load()` open around its calls)
        from .staging import bulk_register

        new, batches = bulk_register(
            self.topics, entries, batch=self.options.durable_restore_batch
        )
        self._durable["restored_subscriptions"] += new
        self._durable["restore_batches"] += batches
        for client, sb in entries:
            cl = self.clients.get(client)
            if cl is not None:
                cl.state.subscriptions.add(sb.filter, sb)
            if self._tenancy is not None and sb.filter[:1] == NS_CHAR:
                t = self._tenancy.tenant_of_topic(sb.filter)
                if t is not None:
                    # seed the durable COUNT quota from restored state:
                    # a tenant over cap after restart keeps its
                    # subscriptions but cannot grow further
                    t.subscriptions_count += 1

    def load_clients(self, v: list) -> None:
        for c in v:
            cl = self.new_client(None, None, c.listener, c.id, False)
            cl.properties.username = c.username
            cl.properties.clean = c.clean
            cl.properties.protocol_version = c.protocol_version
            cl.properties.props = Properties(
                session_expiry_interval=c.properties.session_expiry_interval,
                session_expiry_interval_flag=c.properties.session_expiry_interval_flag,
                authentication_method=c.properties.authentication_method,
                authentication_data=c.properties.authentication_data,
                request_problem_info_flag=c.properties.request_problem_info_flag,
                request_problem_info=c.properties.request_problem_info,
                request_response_info=c.properties.request_response_info,
                receive_maximum=c.properties.receive_maximum,
                topic_alias_maximum=c.properties.topic_alias_maximum,
                user=list(c.properties.user),
                maximum_packet_size=c.properties.maximum_packet_size,
            )
            cl.properties.will = Will(
                payload=c.will.payload,
                user=list(c.will.user),
                topic_name=c.will.topic_name,
                flag=c.will.flag,
                will_delay_interval=c.will.will_delay_interval,
                qos=c.will.qos,
                retain=c.will.retain,
            )
            # restored clients are disconnected and expire normally
            cl.stop(ERR_SERVER_SHUTTING_DOWN())
            expire = (
                cl.properties.protocol_version == 5
                and cl.properties.props.session_expiry_interval == 0
            ) or (cl.properties.protocol_version < 5 and cl.properties.clean)
            self.hooks.on_disconnect(cl, ERR_SERVER_SHUTTING_DOWN(), expire)
            if expire:
                cl.clear_inflights()
                self.unsubscribe_client(cl)
            else:
                self.clients.add_client(cl)

    def load_inflight(self, v: list) -> None:
        # batched restore (ISSUE 17 satellite): the unacked QoS1/QoS2
        # window rides the same chunked bulk path as subscriptions and
        # retained — one inflight-lock acquisition per chunk, and the
        # restore counters prove it was batched
        from .staging import bulk_inflight

        restored, batches = bulk_inflight(
            self.clients, v, batch=self.options.durable_restore_batch
        )
        self._durable["restored_inflight"] += restored
        self._durable["restore_batches"] += batches

    def load_retained(self, v: list) -> None:
        from .staging import bulk_retain

        packets = [msg.to_packet() for msg in v]
        retained, batches = bulk_retain(
            self.topics, packets, batch=self.options.durable_restore_batch
        )
        self._durable["restored_retained"] += retained
        self._durable["restore_batches"] += batches
        self.info.retained = len(self.topics.retained)
        if self._tenancy is not None:
            for pk in packets:
                if pk.payload and pk.topic_name[:1] == NS_CHAR:
                    t = self._tenancy.tenant_of_topic(pk.topic_name)
                    if t is not None:
                        t.retained_count += 1
        if self._retained_engine is not None:
            # one corpus rebuild beats a million note_retained calls
            self._retained_engine.reseed()

    # -- expiry loops (server.go:1696-1758) --------------------------------

    def clear_expired_clients(self, dt: int) -> None:
        """Expire the registry's disconnected sessions that are past their
        expiry interval (``SessionsDue.stopped``: a session enters it when
        its connection ends and leaves with the registry)."""
        due = self._due
        stopped = tuple(due.stopped)
        self._ops.housekeep_visits += len(stopped)
        for client in stopped:
            if client.state.due is not due:
                due.stopped.discard(client)
                continue
            id_ = client.id
            disconnected = client.stop_time
            if disconnected == 0:
                continue
            expire = self.options.capabilities.maximum_session_expiry_interval
            if (
                client.properties.protocol_version == 5
                and client.properties.props.session_expiry_interval_flag
            ):
                expire = client.properties.props.session_expiry_interval
            if disconnected + expire < dt:
                # a pending delayed will fires when the session ends,
                # even if its delay interval has not elapsed
                # [MQTT-3.1.2-8] (ISSUE 20 will fixes): expiry must not
                # orphan the entry — and its retain flag must still be
                # honored after the session object is gone
                pending = self.will_delayed.get(id_)
                if pending is not None:
                    self.will_delayed.delete(id_)
                    if pending.fixed_header.retain:
                        self.topics.retain_message(pending.copy(False))
                        self.info.retained = len(self.topics.retained)
                        if self._retained_engine is not None:
                            self._retained_engine.note_retained(
                                pending.topic_name, True
                            )
                    self.publish_to_subscribers(pending)
                    self.hooks.on_will_sent(client, pending)
                self.hooks.on_client_expired(client)
                self.clients.delete(id_)  # [MQTT-4.1.0-2]

    def clear_expired_retained_messages(self, now: int) -> None:
        for filter_, pk in self.topics.retained.get_all().items():
            expired = pk.protocol_version == 5 and 0 < pk.expiry < now  # [MQTT-3.3.2-5]
            enforced = (
                self.options.capabilities.maximum_message_expiry_interval > 0
                and now - pk.created > self.options.capabilities.maximum_message_expiry_interval
            )
            if expired or enforced:
                self.topics.retained.delete(filter_)
                self.hooks.on_retained_expired(filter_)
                if self._tenancy is not None and filter_[:1] == NS_CHAR:
                    t = self._tenancy.tenant_of_topic(filter_)
                    if t is not None and t.retained_count > 0:
                        t.retained_count -= 1
                if self._retained_engine is not None:
                    self._retained_engine.note_retained(filter_, False)

    def clear_expired_inflights(self, now: int) -> None:
        """Drop the expired in-flight entries of the registry's sessions
        that hold any (``SessionsDue.inflight``: a session's map keeps it
        there while it holds an entry)."""
        due = self._due
        holding = tuple(due.inflight)
        self._ops.housekeep_visits += len(holding)
        for client in holding:
            if client.state.due is not due:
                due.inflight.discard(client)
                continue
            deleted = client.clear_expired_inflights(
                now, self.options.capabilities.maximum_message_expiry_interval
            )
            for id_ in deleted:
                self.hooks.on_qos_dropped(client, Packet(packet_id=id_))

    def send_delayed_lwt(self, dt: int) -> None:
        for id_, pk in self.will_delayed.get_all().items():
            if dt > pk.expiry:
                cl = self.clients.get(id_)
                if (
                    cl is not None
                    and self.overload is not None
                    and not self.overload.admit(cl)
                ):
                    # delayed wills obey the shed accounting too
                    # (ISSUE 20): counted and dropped, never a governor
                    # bypass
                    self.info.messages_dropped += 1
                    if cl.tenant is not None:
                        cl.tenant.messages_dropped += 1
                    cl.properties.will = Will()
                    self.will_delayed.delete(id_)
                    continue
                self.publish_to_subscribers(pk)  # [MQTT-3.1.2-8]
                if pk.fixed_header.retain:
                    if cl is not None:
                        self.retain_message(cl, pk)
                    else:
                        # the retain flag holds even when the session
                        # is already gone (ISSUE 20 will fixes)
                        self.topics.retain_message(pk.copy(False))
                        self.info.retained = len(self.topics.retained)
                        if self._retained_engine is not None:
                            self._retained_engine.note_retained(
                                pk.topic_name, True
                            )
                if cl is not None:
                    cl.properties.will = Will()  # [MQTT-3.1.2-10]
                    self.hooks.on_will_sent(cl, pk)
                self.will_delayed.delete(id_)


def _minimum(a: int, b: int) -> int:
    """Minimum of the non-zero values of a and b; 0 when both are zero
    (server.go:1767-1780)."""
    if a != 0:
        if b != 0 and b < a:
            return b
        return a
    return b
