"""The broker's own ``_Ops`` counts as they leave it: each in a profiler
slice's snapshots (``Server._slice_counters``), on ``/metrics`` and on
``$SYS``, declared once in ``server._OPS_COUNTERS``. The rows below are
written out by hand, not read from that table: a name, a help text or a
path that moves is a change an operator's scrape sees."""

import pytest

from mqtt_tpu import Options
from mqtt_tpu import server as server_mod

from tests.test_server import Harness, run

# (attr, /metrics name, help, $SYS topic or None)
EXPORTED = [
    (
        "socket_sends",
        "mqtt_tpu_socket_sends_total",
        "Calls that reached a client's socket: transport writes (one a "
        "packet, or one a cork) and the native fan-out flush's sends",
        None,
    ),
    (
        "socket_reads",
        "mqtt_tpu_socket_reads_total",
        "Wake-ups of a connection's read loop on data: one recv each (the "
        "stream may join two)",
        None,
    ),
    (
        "direct_reads",
        "mqtt_tpu_direct_reads_total",
        "Of the socket reads, those taken in inside the transport's read "
        "callback by the broker's own protocol (the rest came through a "
        "stream reader)",
        "$SYS/broker/ingest/direct_reads",
    ),
    (
        "ingest_runs",
        "mqtt_tpu_ingest_runs_total",
        "Runs of PUBLISH frames a read loop handed to the ingest run in one "
        "call that took at least one publish",
        "$SYS/broker/ingest/runs",
    ),
    (
        "ingest_run_publishes",
        "mqtt_tpu_ingest_run_publishes_total",
        "Publishes taken in by ingest runs (the rest took the per-frame "
        "path: mqtt_tpu_messages_received_total has both)",
        "$SYS/broker/ingest/run_publishes",
    ),
    (
        "ack_runs",
        "mqtt_tpu_ack_runs_total",
        "Stretches of bare PUBACK frames a read loop handed to the ack run "
        "in one call that took them",
        "$SYS/broker/ingest/ack_runs",
    ),
    (
        "ack_run_acks",
        "mqtt_tpu_ack_run_acks_total",
        "PUBACK frames taken in by ack runs (the rest took the per-frame "
        "path)",
        "$SYS/broker/ingest/ack_run_acks",
    ),
    (
        "slice_targets_max",
        "mqtt_tpu_stage_slice_targets_max",
        "Most target ids one completion slice of a staged batch has looked "
        "up at once (high-water mark since start)",
        "$SYS/broker/overload/stage_slice_targets_max",
    ),
    (
        "deliveries_flush",
        "mqtt_tpu_deliveries_flush_total",
        "Deliveries of the encode-once fan-out that left by the native "
        "flush (a ready socket: idle transport, empty queue)",
        "$SYS/broker/egress/deliveries_flush",
    ),
    (
        "deliveries_cork",
        "mqtt_tpu_deliveries_cork_total",
        "Deliveries of the encode-once fan-out that joined a socket's open "
        "cork (its read's, or the completion slice's)",
        "$SYS/broker/egress/deliveries_cork",
    ),
    (
        "deliveries_queue",
        "mqtt_tpu_deliveries_queue_total",
        "Deliveries of the encode-once fan-out that took a socket's bounded "
        "outbound queue (one write a frame)",
        "$SYS/broker/egress/deliveries_queue",
    ),
    (
        "deliveries_dropped_full",
        "mqtt_tpu_deliveries_dropped_full_total",
        "Deliveries of the encode-once fan-out the outbound queue refused, "
        "full (also in mqtt_tpu_messages_dropped_total)",
        "$SYS/broker/egress/deliveries_dropped_full",
    ),
    (
        "cork_writes",
        "mqtt_tpu_cork_writes_total",
        "Corks written: one transport write for the packets a socket's read "
        "or a completion slice held back",
        "$SYS/broker/egress/cork_writes",
    ),
    (
        "cork_frames",
        "mqtt_tpu_cork_frames_total",
        "Packets that joined a socket's cork",
        "$SYS/broker/egress/cork_frames",
    ),
    (
        "cork_early_writes",
        "mqtt_tpu_cork_early_writes_total",
        "Of the corks written, those a cork past its byte bound forced "
        "before its opener closed it",
        "$SYS/broker/egress/cork_early_writes",
    ),
    (
        "socket_checks",
        "mqtt_tpu_socket_checks_total",
        "Times the encode-once fan-out read a socket's readiness: once a "
        "delivery, or once a completion slice for a socket the slice corked",
        "$SYS/broker/egress/socket_checks",
    ),
    (
        "share_groups",
        "mqtt_tpu_share_groups_total",
        "Shared subscription groups a publish's fan-out selected a member "
        "of",
        "$SYS/broker/fanout/share_groups",
    ),
    (
        "share_picks_offline",
        "mqtt_tpu_share_picks_offline_total",
        "Selected shared-group members with no connected client (an "
        "offline session was chosen: nothing is written)",
        "$SYS/broker/fanout/share_picks_offline",
    ),
    (
        "share_select_ns",
        "mqtt_tpu_share_select_seconds_total",
        "Time of shared-group selection while a profiler session was live: "
        "forced materialization, the hooks' pick, select and merge",
        "$SYS/broker/fanout/share_select_ns",
    ),
    (
        "share_groups_early",
        "mqtt_tpu_share_groups_early_total",
        "Shared subscription groups a completion slice picked a member of "
        "before it read its targets",
        "$SYS/broker/fanout/share_groups_early",
    ),
    (
        "qos_begins",
        "mqtt_tpu_qos_begins_total",
        "QoS>0 deliveries of the encode-once fan-out that reached the "
        "inflight bookkeeping",
        "$SYS/broker/fanout/qos_begins",
    ),
    (
        "qos_begins_hooked",
        "mqtt_tpu_qos_begins_hooked_total",
        "Of those, the ones that dispatched on_qos_publish (a hook provides "
        "it)",
        "$SYS/broker/fanout/qos_begins_hooked",
    ),
    (
        "qos_begin_ns",
        "mqtt_tpu_qos_begin_seconds_total",
        "Time of the encode-once fan-out's QoS>0 bookkeeping while a "
        "profiler session was live: the entry's clone, the inflight store, "
        "the hooks",
        "$SYS/broker/fanout/qos_begin_ns",
    ),
    (
        "qos_runs",
        "mqtt_tpu_qos_runs_total",
        "Runs of one socket's QoS>0 deliveries in a completion slice whose "
        "inflight bookkeeping was settled at once",
        "$SYS/broker/fanout/qos_runs",
    ),
    (
        "qos_run_entries",
        "mqtt_tpu_qos_run_entries_total",
        "QoS>0 deliveries whose bookkeeping a run settled (the rest were "
        "begun one at a time)",
        "$SYS/broker/fanout/qos_run_entries",
    ),
    (
        "housekeep_ticks",
        "mqtt_tpu_housekeep_ticks_total",
        "Housekeeping ticks: the once-a-second expiry and eviction passes",
        "$SYS/broker/housekeep/ticks",
    ),
    (
        "housekeep_visits",
        "mqtt_tpu_housekeep_visits_total",
        "Sessions the housekeeping passes looked at: those holding in-flight "
        "entries, disconnected ones awaiting expiry, and those with a "
        "transport or queue backlog",
        "$SYS/broker/housekeep/visits",
    ),
    (
        "housekeep_ns",
        "mqtt_tpu_housekeep_seconds_total",
        "Time of the housekeeping ticks' expiry and eviction passes while a "
        "profiler session was live",
        "$SYS/broker/housekeep/ns",
    ),
    (
        "sys_publish_ns",
        "mqtt_tpu_sys_publish_seconds_total",
        "Time of the housekeeping tick's $SYS publish while a profiler "
        "session was live",
        "$SYS/broker/housekeep/sys_publish_ns",
    ),
]


def _exported(attr, bump):
    """Bump one count on a served broker; what the snapshot, the
    exposition and the $SYS tree then say."""

    async def scenario():
        h = Harness(Options(inline_client=True))
        srv = h.server
        await srv.serve()
        try:
            setattr(srv._ops, attr, getattr(srv._ops, attr) + bump)
            value = getattr(srv._ops, attr)
            snap = srv._slice_counters()
            text = srv.telemetry.registry.exposition()
            srv.publish_sys_topics()
            tree = {
                p.topic_name: bytes(p.payload).decode()
                for p in srv.topics.messages("$SYS/#")
            }
        finally:
            await srv.close()
            await h.shutdown()
        return value, snap, text, tree

    return run(scenario())


@pytest.mark.parametrize(
    "attr, metric, help_, sys_topic", EXPORTED, ids=[r[0] for r in EXPORTED]
)
def test_a_count_reads_the_same_everywhere(attr, metric, help_, sys_topic):
    seconds = metric.endswith("_seconds_total")
    value, snap, text, tree = _exported(attr, 2_500_000_000 if seconds else 12_345)
    assert snap[attr] == value
    lines = text.splitlines()
    assert f"# HELP {metric} {help_}" in lines
    kind = "counter" if metric.endswith("_total") else "gauge"
    assert f"# TYPE {metric} {kind}" in lines
    sample = [l for l in lines if l.startswith(metric + " ")]
    assert len(sample) == 1, sample
    exported = float(sample[0].split(" ")[1])
    assert exported == (value / 1e9 if seconds else value)
    if sys_topic is None:
        assert not any(t.endswith("/" + attr) for t in tree), attr
    else:
        assert tree[sys_topic] == str(value)


def test_every_ops_count_has_one_row():
    """An ``int`` on ``_Ops`` is an exported count, and a row names one."""
    ops = Harness(Options(inline_client=True)).server._ops
    counts = {k for k, v in vars(ops).items() if type(v) is int}
    attrs = [c.attr for c in server_mod._OPS_COUNTERS]
    assert len(attrs) == len(set(attrs))
    assert counts == set(attrs)
    assert {r[0] for r in EXPORTED} == counts
