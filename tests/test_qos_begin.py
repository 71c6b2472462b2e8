"""The encode-once fan-out's QoS>0 bookkeeping (``Server._flush_variant``,
``Inflight.begin``): a delivery's inflight entry is a clone of its
variant's prototype, stored under one acquisition of the session's
inflight lock, and ``on_qos_publish`` is dispatched only where a hook
provides it. Differential against the bookkeeping it replaced, kept here
as the oracle: a ``copy(False)`` of the publish a target with its topic,
QoS, retain flag and packet id written, and the cap, the id walk, the
store, the send quota and its park as three calls on the map. Entries
equal field for field, frames byte for byte, through every way out (a
slice's cork, the native flush, the outbound queue) and through
``publish_to_client``. A slice's QoS>0 deliveries to a socket it keeps a
record of are begun as one run (``Server._settle_run``,
``Inflight.begin_run``): held to the same slices begun a delivery at a
time, write for write, through refusals, parks, an early cork write and
every way a record ends. CPU backend: entries, bytes and counts."""

import asyncio
import logging
import threading

import pytest

from mqtt_tpu import Options
from mqtt_tpu.hooks import ON_PACKET_ID_EXHAUSTED, ON_QOS_PUBLISH, Hook
from mqtt_tpu.inflight import Inflight
from mqtt_tpu.packets import (
    PUBLISH,
    FixedHeader,
    Packet,
    Properties,
    Subscription,
    UserProperty,
    encode_packet,
)
from mqtt_tpu.packets.codec import decode_length
from mqtt_tpu.server import Capabilities
from mqtt_tpu.staging import Parked

from tests.test_batch_completion import SliceRig, drained
from tests.test_server import run

PING = b"\xd0\x00"  # a PINGRESP: a frame that holds a socket's queue


def legacy_entry(dpk, eff, retain, pid):
    """A delivery's inflight entry as the per-target copy made it."""
    out = dpk.copy(False)
    out.topic_name = dpk.topic_name
    out.fixed_header.qos = eff
    out.fixed_header.retain = retain
    out.packet_id = pid
    return out


class Shadow:
    """One session's inflight ids, cursor and send quota, moved as the
    three calls moved them."""

    def __init__(self, cl):
        inf = cl.state.inflight
        self.entries = dict(inf.internal)
        self.cursor = cl.state.packet_id
        self.quota = inf.send_quota
        self.maximum_quota = inf.maximum_send_quota

    def begin(self, dpk, eff, cap, maximum):
        """The entry stored and whether it is written now; (None, False)
        for a refusal."""
        if len(self.entries) >= cap:
            return None, False
        i = self.cursor
        started, overflowed = i, False
        while True:
            if overflowed and i == started:
                return None, False
            if i >= maximum:
                overflowed = True
                i = 0
                continue
            i += 1
            if (i & 0xFFFF) not in self.entries:
                break
        self.cursor = i
        out = legacy_entry(dpk, eff, False, i & 0xFFFF)
        sent_quota = self.quota
        self.entries[out.packet_id] = out
        if self.quota > 0:
            self.quota -= 1
        if sent_quota == 0 and self.maximum_quota > 0:
            out.expiry = -1
            return out, False
        return out, True


def frames_of(raw):
    """A socket's bytes as frames, the PINGRESPs that held its queue out."""
    out, at = [], 0
    while at < len(raw):
        n, body = decode_length(raw, at + 1)
        out.append(raw[at:body + n])
        at = body + n
    return [f for f in out if f != PING]


def wire(entry, version):
    w = entry.copy(True)  # a copy resets DUP: it is the entry's
    w.fixed_header.dup = entry.fixed_header.dup
    w.protocol_version = version
    return encode_packet(w)


def publish(source, qos, payload, origin):
    if source == "ingest":
        # as the ingest run makes it: nothing but what a v4 PUBLISH holds
        pk = Packet.inbound_publish(
            FixedHeader(PUBLISH, False, qos, False, 0), "t/a", payload, 9, 4
        )
    else:
        pk = Packet(
            fixed_header=FixedHeader(type=PUBLISH, qos=qos),
            protocol_version=5, topic_name="t/a", payload=payload,
            packet_id=9,
            properties=Properties(
                content_type="text/plain", user=[UserProperty("k", "v")],
                topic_alias=3, topic_alias_flag=True,
            ),
        )
    pk.origin = origin
    pk.created = 1_700_000_000
    return pk


class Recorder(Hook):
    """Provides ``events``; keeps what each call of a QoS hook saw."""

    def __init__(self, events=(ON_QOS_PUBLISH,)):
        super().__init__()
        self.events = events
        self.calls = []

    def id(self):
        return "recorder"

    def provides(self, b):
        return b in self.events

    def on_qos_publish(self, cl, pk, sent, resends):
        self.calls.append((cl.id, pk, pk.expiry, pk.packet_id))

    def on_packet_id_exhausted(self, cl, pk):
        self.calls.append((cl.id, pk, "exhausted", None))


def qos_counts(srv):
    ops = srv._ops
    return ops.qos_begins, ops.qos_begins_hooked


class Rig:
    """A SliceRig broker with a publisher and subscribers on ``t/#`` at
    QoS 2, and the oracle's shadow of each subscriber."""

    def __init__(self, monkeypatch, options=None):
        self.slice = SliceRig(monkeypatch, options)
        self.server = self.slice.server

    async def join(self, n, version=4, props=None):
        self.pub, *_ = await self.slice.join("pub")
        self.subs, self.readers = [], {}
        for k in range(n):
            cl, r, *_ = await self.slice.join(
                f"s{k}", "t/#", version=version, qos=2, props=props
            )
            self.subs.append(cl)
            self.readers[cl.id] = r

    def shadows(self):
        return {cl.id: Shadow(cl) for cl in self.subs}

    def flush(self, dpk, eff, group=None):
        """One variant through ``_flush_variant``, as ``_fan_out_batched``
        hands it over."""
        srv = self.server
        group = self.subs if group is None else group
        version = group[0].properties.protocol_version
        data, id_off = srv._encode_variant(dpk, version | eff << 3, None)
        srv._flush_variant(
            dpk, eff, False, data, id_off, [(cl, None) for cl in group], False
        )

    def slice_of(self, pks):
        """The publishes as ONE completion slice: every subscriber is hit
        once a publish, so the slice keeps a record of its socket and
        each delivery is an append to its cork."""
        srv = self.server
        entries = [Parked(None, None, None, None, self.pub, pk) for pk in pks]
        results = [srv.topics.subscribers(pk.topic_name) for pk in pks]
        srv._complete_staged(entries, results)


@pytest.mark.parametrize("source", ["ingest", "props"])
@pytest.mark.parametrize("version", [4, 5])
@pytest.mark.parametrize("qos", [1, 2])
@pytest.mark.parametrize("route", ["cork", "flush", "queue", "per_target"])
def test_entries_and_frames_equal_the_per_target_copy(
    monkeypatch, route, qos, version, source
):
    """Three publishes to three subscribers, one of whose walks wraps at
    ``maximum_packet_id`` and one of whose walks passes held ids over."""

    async def scenario():
        rig = Rig(monkeypatch)
        srv = rig.server
        await rig.join(3, version=version)
        caps = srv.options.capabilities
        caps.maximum_packet_id = 6
        s0, s1, s2 = rig.subs
        s1.state.packet_id = 5
        s1.state.inflight.set(legacy_entry(publish(source, 1, b"h", "x"), 1, False, 6))
        for pid in (1, 2):
            s2.state.inflight.set(
                legacy_entry(publish(source, 1, b"h", "x"), 1, False, pid)
            )
        pks = [publish(source, qos, b"p%d" % i, rig.pub.id) for i in range(3)]
        shadows = rig.shadows()
        before = qos_counts(srv)
        if route == "cork":
            rig.slice_of(pks)
        for pk in pks:
            if route == "per_target":
                for cl in rig.subs:
                    srv.publish_to_client(cl, Subscription(filter="t/#", qos=2), pk)
            elif route == "queue":
                for cl in rig.subs:
                    cl.state.outbound.put_nowait(PING)  # not ready
                rig.flush(pk, qos)
            elif route == "flush":
                rig.flush(pk, qos)
        written = {cl.id: [] for cl in rig.subs}
        for pk in pks:
            for cl in rig.subs:
                entry, now = shadows[cl.id].begin(
                    pk, qos, caps.maximum_inflight, caps.maximum_packet_id
                )
                if now:
                    written[cl.id].append(entry)
        # the entries, field for field, each with a header of its own
        headers = set()
        for cl in rig.subs:
            got = cl.state.inflight.internal
            assert got == shadows[cl.id].entries
            assert cl.state.packet_id == shadows[cl.id].cursor
            assert cl.state.inflight.send_quota == shadows[cl.id].quota
            for e in got.values():
                headers.add(id(e.fixed_header))
                if "properties" in vars(pks[0]):
                    assert all(e.properties is not pk.properties for pk in pks)
        assert len(headers) == sum(len(s.entries) for s in shadows.values())
        assert [
            e.packet_id for e in shadows["s1"].entries.values()
        ] == [6, 1, 2, 3]  # wrapped
        assert sorted(shadows["s2"].entries) == [1, 2, 3, 4, 5]
        # the frames, byte for byte
        raw = await drained(srv, rig.readers)
        for cl in rig.subs:
            assert frames_of(raw[cl.id]) == [wire(e, version) for e in written[cl.id]]
        begun = 0 if route == "per_target" else 9
        assert qos_counts(srv) == (before[0] + begun, before[1])
        await rig.slice.h.shutdown()

    run(scenario())


class TestRefusals:
    def test_exhausted_ids_are_a_drop_and_one_hook_call(self, monkeypatch, caplog):
        async def scenario():
            rig = Rig(monkeypatch)
            srv = rig.server
            hook = Recorder((ON_PACKET_ID_EXHAUSTED,))
            srv.add_hook(hook)
            await rig.join(2)
            srv.options.capabilities.maximum_packet_id = 3
            s0, s1 = rig.subs
            for pid in (1, 2, 3):
                s0.state.inflight.set(legacy_entry(publish("ingest", 1, b"h", "x"), 1, False, pid))
            s0.state.packet_id = 3
            held = dict(s0.state.inflight.internal)
            dropped = srv.info.inflight_dropped
            pk = publish("ingest", 1, b"x", rig.pub.id)
            with caplog.at_level(logging.WARNING):
                rig.flush(pk, 1)
            assert hook.calls == [("s0", pk, "exhausted", None)]
            assert srv.info.inflight_dropped == dropped + 1
            assert "packet ids exhausted" in caplog.text
            assert s0.state.inflight.internal == held and s0.state.packet_id == 3
            raw = await drained(srv, rig.readers)
            assert frames_of(raw["s0"]) == []
            assert frames_of(raw["s1"]) == [wire(legacy_entry(pk, 1, False, 1), 4)]
            await rig.slice.h.shutdown()

        run(scenario())

    def test_the_inflight_cap_is_a_drop(self, monkeypatch, caplog):
        async def scenario():
            rig = Rig(monkeypatch)
            srv = rig.server
            hook = Recorder((ON_PACKET_ID_EXHAUSTED,))
            srv.add_hook(hook)
            await rig.join(2)
            srv.options.capabilities.maximum_inflight = 1
            s0, s1 = rig.subs
            s0.state.inflight.set(legacy_entry(publish("ingest", 1, b"h", "x"), 1, False, 9))
            dropped = srv.info.inflight_dropped
            pk = publish("ingest", 1, b"x", rig.pub.id)
            with caplog.at_level(logging.WARNING):
                rig.flush(pk, 1)
            assert srv.info.inflight_dropped == dropped + 1
            assert "client store quota reached" in caplog.text
            assert hook.calls == [] and list(s0.state.inflight.internal) == [9]
            raw = await drained(srv, rig.readers)
            assert frames_of(raw["s0"]) == []
            assert len(frames_of(raw["s1"])) == 1
            await rig.slice.h.shutdown()

        run(scenario())

    @pytest.mark.parametrize("hooked", [False, True])
    def test_the_send_quota_parks_the_entry(self, monkeypatch, hooked):
        """A v5 session that accepts one delivery in flight: the second
        is stored, marked for resend (expiry -1) and not written. A hook
        sees it as the three calls showed it: before the mark."""

        async def scenario():
            rig = Rig(monkeypatch)
            srv = rig.server
            hook = Recorder()
            if hooked:
                srv.add_hook(hook)
            await rig.join(1, version=5, props=Properties(receive_maximum=1))
            (s0,) = rig.subs
            assert s0.state.inflight.maximum_send_quota == 1
            shadow = Shadow(s0)
            caps = srv.options.capabilities
            inflight = srv.info.inflight
            pks = [publish("props", 1, b"q%d" % i, rig.pub.id) for i in range(2)]
            for pk in pks:
                rig.flush(pk, 1)
            first, now = shadow.begin(pks[0], 1, caps.maximum_inflight, 0xFFFF)
            assert now
            second, now = shadow.begin(pks[1], 1, caps.maximum_inflight, 0xFFFF)
            assert not now and second.expiry == -1
            assert s0.state.inflight.internal == {1: first, 2: second}
            assert s0.state.inflight.send_quota == 0
            assert s0.state.inflight.next_immediate() is s0.state.inflight.internal[2]
            assert srv.info.inflight == inflight + 2
            raw = await drained(srv, rig.readers)
            assert frames_of(raw["s0"]) == [wire(first, 5)]
            if hooked:
                got = s0.state.inflight.internal
                assert hook.calls == [
                    ("s0", got[1], 0, 1), ("s0", got[2], 0, 2)
                ]
            await rig.slice.h.shutdown()

        run(scenario())

    def test_a_full_queue_rolls_the_entry_back(self, monkeypatch):
        async def scenario():
            opts = Options(
                capabilities=Capabilities(maximum_client_writes_pending=2)
            )
            rig = Rig(monkeypatch, opts)
            srv = rig.server
            await rig.join(2)
            s0, s1 = rig.subs
            quota = s0.state.inflight.send_quota
            for _ in range(2):
                s0.state.outbound.put_nowait(PING)  # full
            dropped = srv.info.messages_dropped
            full = srv._ops.deliveries_dropped_full
            pk = publish("ingest", 1, b"x", rig.pub.id)
            rig.flush(pk, 1)
            assert srv._ops.deliveries_dropped_full == full + 1
            assert srv.info.messages_dropped == dropped + 1
            assert s0.state.inflight.internal == {}
            assert s0.state.inflight.send_quota == quota
            assert s0.state.packet_id == 1  # the cursor stays where it went
            assert list(s1.state.inflight.internal) == [1]
            raw = await drained(srv, rig.readers)
            assert frames_of(raw["s0"]) == []
            assert frames_of(raw["s1"]) == [wire(legacy_entry(pk, 1, False, 1), 4)]
            await rig.slice.h.shutdown()

        run(scenario())


def test_a_resend_sets_dup_on_its_own_entry_only(monkeypatch):
    async def scenario():
        rig = Rig(monkeypatch)
        srv = rig.server
        await rig.join(3)
        pk = publish("ingest", 1, b"x", rig.pub.id)
        rig.flush(pk, 1)
        await drained(srv, rig.readers)
        s0, s1, s2 = rig.subs
        s0.resend_inflight_messages(True)
        assert s0.state.inflight.internal[1].fixed_header.dup is True
        for cl in (s1, s2):
            assert cl.state.inflight.internal[1].fixed_header.dup is False
        assert pk.fixed_header.dup is False
        raw = await drained(srv, rig.readers)
        want = legacy_entry(pk, 1, False, 1)
        want.fixed_header.dup = True
        assert frames_of(raw["s0"]) == [wire(want, 4)]
        assert raw["s0"][0] & 0x08  # DUP on the wire
        assert frames_of(raw["s1"]) == frames_of(raw["s2"]) == []
        await rig.slice.h.shutdown()

    run(scenario())


class TestTheHookDispatch:
    @pytest.mark.parametrize("route", ["flush", "cork"])
    def test_a_qos_hook_sees_each_new_entry_once(self, monkeypatch, route):
        """One call a new entry, each with the entry it was stored as
        (its own packet), and ``qos_hook_dispatch_share`` 100 beside it."""

        async def scenario():
            rig = Rig(monkeypatch)
            srv = rig.server
            hook = Recorder()
            srv.add_hook(hook)
            await rig.join(3)
            pks = [publish("ingest", 1, b"p%d" % i, rig.pub.id) for i in range(2)]
            begins, hooked = qos_counts(srv)
            if route == "cork":
                rig.slice_of(pks)
            else:
                for pk in pks:
                    rig.flush(pk, 1)
            entries = [
                cl.state.inflight.internal[pid]
                for pid in (1, 2) for cl in rig.subs
            ]
            assert sorted(
                (c[0], c[3]) for c in hook.calls
            ) == sorted((cl.id, pid) for pid in (1, 2) for cl in rig.subs)
            assert {id(c[1]) for c in hook.calls} == {id(e) for e in entries}
            for cid, pk, expiry, pid in hook.calls:
                assert srv.clients.get(cid).state.inflight.internal[pid] is pk
            after = qos_counts(srv)
            assert after[0] - begins == after[1] - hooked == 6
            share = 100.0 * (after[1] - hooked) / (after[0] - begins)
            assert share == 100.0
            await rig.slice.h.shutdown()

        run(scenario())

    def test_a_hook_added_after_the_first_publish_is_seen(self, monkeypatch):
        async def scenario():
            rig = Rig(monkeypatch)
            srv = rig.server
            await rig.join(2)
            rig.flush(publish("ingest", 1, b"a", rig.pub.id), 1)
            assert qos_counts(srv)[1] == 0
            hook = Recorder()
            srv.add_hook(hook)
            rig.flush(publish("ingest", 1, b"b", rig.pub.id), 1)
            assert [(c[0], c[3]) for c in hook.calls] == [("s0", 2), ("s1", 2)]
            assert qos_counts(srv) == (4, 2)
            await rig.slice.h.shutdown()

        run(scenario())

    def test_no_qos_hook_no_dispatch(self, monkeypatch):
        """A hook that provides other events is not asked."""

        async def scenario():
            rig = Rig(monkeypatch)
            srv = rig.server
            hook = Recorder((ON_PACKET_ID_EXHAUSTED,))
            srv.add_hook(hook)
            await rig.join(2)
            called = []
            monkeypatch.setattr(
                srv.hooks, "on_qos_publish", lambda *a: called.append(a)
            )
            rig.flush(publish("ingest", 1, b"a", rig.pub.id), 1)
            assert called == [] and qos_counts(srv) == (2, 0)
            await rig.slice.h.shutdown()

        run(scenario())


# -- a slice's run: one socket's QoS>0 deliveries begun together --------

RUN_CASES = [
    "slices", "cap", "exhausted", "quota", "early_cut",
    "enqueue_frame", "publish_to_client", "stop", "stop_from_another_loop",
    "hooked",
]
# what the run moves that the per-delivery bookkeeping does not: its own
# counts, the hooked begins the oracle's forced gate counts, the time
RUN_ONLY = {"qos_runs", "qos_run_entries", "qos_begins_hooked", "qos_begin_ns"}


def outcome(monkeypatch, case, per_delivery):
    """Two completion slices of QoS1 publishes to three subscribers whose
    sockets the slices keep records of, set up as ``case`` says; with
    ``per_delivery`` the ``on_qos_publish`` gate is forced shut, so each
    delivery is begun on its own (``Inflight.begin``) with no hook to
    call: the oracle. What the sessions, the sockets, the hooks and the
    counts came to."""
    import mqtt_tpu.clients as clients_mod

    async def scenario():
        rig = Rig(monkeypatch)
        srv = rig.server
        hook = Recorder(
            (ON_QOS_PUBLISH, ON_PACKET_ID_EXHAUSTED) if case == "hooked"
            else (ON_PACKET_ID_EXHAUSTED,)
        )
        srv.add_hook(hook)
        if case == "quota":
            await rig.join(3, version=5, props=Properties(receive_maximum=2))
        else:
            await rig.join(3)
        s0, s1, s2 = rig.subs
        caps = srv.options.capabilities
        held = publish("ingest", 1, b"h", "x")
        if case == "cap":
            caps.maximum_inflight = 3
            s1.state.inflight.set(legacy_entry(held, 1, False, 2))
        elif case == "exhausted":
            caps.maximum_packet_id = 4
            for pid in (1, 3):
                s0.state.inflight.set(legacy_entry(held, 1, False, pid))
        elif case == "early_cut":
            # three frames fill the cork; s1's third and later are refused
            # at the cap, so that its frames would cross the bound early
            n = len(srv._encode_variant(held, 4 | 1 << 3, None)[0])
            monkeypatch.setattr(clients_mod, "CORK_MAX_BYTES", 3 * n)
            caps.maximum_inflight = 5
            for pid in (7, 8, 9):
                s1.state.inflight.set(legacy_entry(held, 1, False, pid))
        extra = publish("ingest", 1, b"extra", rig.pub.id)
        # the deliveries a stop left in s2's run (None: no stop yet)
        left = []

        async def stop_there():
            s2.stop()

        def after(payload):
            if payload != b"p1":
                return
            if case == "enqueue_frame":
                srv._enqueue_frame(s0, PING, lambda: None)
            elif case == "publish_to_client":
                srv.publish_to_client(s1, Subscription(filter="t/#", qos=2), extra)
            elif case.startswith("stop"):
                rec = s2._slice
                if case == "stop":
                    s2.stop()
                else:
                    # the stop a takeover makes from another loop (a loop
                    # shard's), while this loop is inside the slice
                    t = threading.Thread(target=asyncio.run, args=(stop_there(),))
                    t.start()
                    t.join()
                left.append(len(rec.entries))

        rig.slice.after_publish = after
        if per_delivery:
            gate = srv._no_hook_provides
            monkeypatch.setattr(
                srv, "_no_hook_provides",
                lambda name, events: name != "qos_publish" and gate(name, events),
            )
        pks = [publish("ingest", 1, b"p%d" % i, rig.pub.id) for i in range(6)]
        before = srv._slice_counters()
        info0 = vars(srv.info).copy()
        del rig.slice.log[:]
        rig.slice_of(pks[:4])
        rig.slice_of(pks[4:])
        after_counts = srv._slice_counters()
        moved = {
            k: after_counts[k] - before[k]
            for k in after_counts
            if isinstance(after_counts[k], int) and k not in RUN_ONLY
        }
        info = {
            k: v - info0[k] for k, v in vars(srv.info).items()
            if isinstance(v, int) and k in info0
        }
        sessions = {
            cl.id: (
                dict(cl.state.inflight.internal), cl.state.packet_id,
                cl.state.inflight.send_quota, cl.state.out_bytes,
                cl.state.out_writes,
            )
            for cl in rig.subs
        }
        runs = (
            after_counts["qos_runs"] - before["qos_runs"],
            after_counts["qos_run_entries"] - before["qos_run_entries"],
            after_counts["qos_begins_hooked"] - before["qos_begins_hooked"],
            left,
        )
        writes = list(rig.slice.log)
        raw = await drained(srv, rig.readers)
        await rig.slice.h.shutdown()
        return {
            "sessions": sessions,
            "counts": moved,
            "info": info,
            "writes": writes,
            "frames": {cid: frames_of(b) for cid, b in raw.items()},
            # a session's hook calls in order (a run tells its refusals
            # when it is settled: sessions interleave otherwise)
            "hook": {
                cl.id: [c[1:] for c in hook.calls if c[0] == cl.id]
                for cl in rig.subs
            },
        }, runs

    return run(scenario())


@pytest.mark.parametrize("case", RUN_CASES)
def test_a_run_leaves_what_the_per_delivery_bookkeeping_leaves(
    monkeypatch, case
):
    """Entries field for field, ids, cursor, quota, ``info.inflight``,
    every transport write and the frames byte for byte, the hooks' calls
    in order and every count: a slice's runs against the same slices
    begun a delivery at a time."""
    got, runs = outcome(monkeypatch, case, per_delivery=False)
    want, oracle_runs = outcome(monkeypatch, case, per_delivery=True)
    assert got == want
    assert oracle_runs[:2] == (0, 0)
    begun = got["counts"]["qos_begins"]
    sessions = got["sessions"]
    if case == "hooked":
        # a provider of on_qos_publish: no run, each entry dispatched
        assert runs[:3] == (0, 0, begun)
        assert [c[2] for c in got["hook"]["s0"]] == [1, 2, 3, 4, 5, 6]
        return
    assert runs[2] == 0 and runs[0] >= 3 and runs[1] > 0
    assert got["info"]["inflight"] == sum(
        len(s[0]) for s in sessions.values()
    ) - sum(len(held) for held in want_held(case).values())
    for entries, *_ in sessions.values():
        assert all(e.packet_id == pid for pid, e in entries.items())
    if case in ("slices", "quota", "cap", "exhausted"):
        # every delivery joined a run
        assert runs[1] == begun == 18
    if case == "slices":
        assert runs[:3] == (6, 18, 0)
        for entries, cursor, *_ in sessions.values():
            assert sorted(entries) == list(range(1, 7)) and cursor == 6
    elif case == "cap":
        assert got["info"]["inflight_dropped"] == 3 + 4 + 3  # s1 holds one
    elif case == "exhausted":
        # s0 holds ids 1 and 3 of 4: two deliveries take 2 and 4
        assert [
            bytes(c[0].payload) for c in got["hook"]["s0"]
        ] == [b"p2", b"p3", b"p4", b"p5"]
        assert all(c[1] == "exhausted" for c in got["hook"]["s0"])
        assert len(got["hook"]["s1"]) == len(got["hook"]["s2"]) == 2
    elif case == "quota":
        for entries, _cursor, quota, *_ in sessions.values():
            assert quota == 0
            assert [e.expiry for e in entries.values()] == [0, 0] + [-1] * 4
        assert all(len(f) == 2 for f in got["frames"].values())
    elif case == "early_cut":
        assert got["counts"]["cork_early_writes"] == 2  # s0's and s2's
        # their records end at the cut: p3 is begun on its own
        assert runs[1] == begun - 2
        assert len(got["frames"]["s1"]) == 2
    elif case == "publish_to_client":
        # the run's ids were taken before the per-target one: in order
        entries = sessions["s1"][0]
        assert [bytes(entries[pid].payload) for pid in sorted(entries)] == [
            b"p0", b"p1", b"extra", b"p2", b"p3", b"p4", b"p5"
        ]
    elif case.startswith("stop"):
        # the stopped session keeps the entries of what it was sent
        assert sorted(sessions["s2"][0]) == [1, 2]
        assert len(got["frames"]["s2"]) == 2
        # the owner's stop settles the run; another loop's only drops
        # the record, and the slice settles the run at its end
        assert runs[3] == [0 if case == "stop" else 2]
    elif case == "enqueue_frame":
        assert got["counts"]["deliveries_queue"] == 4  # s0's p2..p5


def want_held(case):
    """The entries a case's sessions held before its slices."""
    return {
        "cap": {"s1": [2]},
        "exhausted": {"s0": [1, 3]},
        "early_cut": {"s1": [7, 8, 9]},
    }.get(case, {})


@pytest.mark.parametrize("stored", [0, 1])
def test_a_run_that_fails_to_settle_sends_none_of_its_frames(
    monkeypatch, stored
):
    """A run whose bookkeeping raises (``Inflight.begin_run``, here after
    storing ``stored`` of its entries): none of its frames reaches a
    socket, so none leaves with the variant's placeholder id, and the
    counts their appends moved are taken back; what the failed call
    stored stays in the session, counted in ``info.inflight``."""

    def failing(self, state, entries, cap, maximum):
        with self._lock:
            for pk in entries[:stored]:
                self._begin_locked(state, pk, cap, maximum)
        raise RuntimeError("injected")

    async def scenario():
        rig = Rig(monkeypatch)
        srv = rig.server
        await rig.join(2)
        monkeypatch.setattr(Inflight, "begin_run", failing)
        before = srv._slice_counters()
        inflight0 = srv.info.inflight
        rig.slice_of(
            [publish("ingest", 1, b"p%d" % i, rig.pub.id) for i in range(3)]
        )
        after = srv._slice_counters()
        raw = await drained(srv, rig.readers)
        await rig.slice.h.shutdown()
        assert raw == {"s0": b"", "s1": b""}
        assert [len(cl.state.inflight) for cl in rig.subs] == [stored] * 2
        assert srv.info.inflight - inflight0 == 2 * stored
        for k in ("cork_frames", "deliveries_cork", "qos_runs"):
            assert after[k] == before[k], k
        assert after["qos_begins"] - before["qos_begins"] == 6

    run(scenario())
