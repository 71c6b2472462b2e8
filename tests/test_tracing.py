"""The end-to-end trace plane (mqtt_tpu.tracing): span-tree integrity
and parent/child timing invariants through a real staged broker, seeded
sampling determinism, the cross-worker trace join over a 2-worker mesh,
exemplar -> flight-dump cross-linking, the pure-Python trace-event
validator, the device duty-cycle profiler's window math, and the
/traces HTTP matrix (PR 3 conventions)."""

import asyncio
import json
import time

import pytest

from mqtt_tpu import Options, Server
from mqtt_tpu.cluster import Cluster
from mqtt_tpu.listeners import Config as LConfig, HTTPStats
from mqtt_tpu.packets import (
    PUBACK,
    PUBLISH,
    SUBACK,
    Properties,
    Subscription,
    UserProperty,
)
from mqtt_tpu.telemetry import (
    DEVICE_SUBSTAGES,
    TRACE_USER_PROPERTY,
    Telemetry,
    check_exposition,
)
from mqtt_tpu.tracing import DeviceProfiler, Tracer, check_trace_events

from tests.test_server import (
    Harness,
    pub_packet,
    read_wire_packet,
    run,
    sub_packet,
)

# slop for exported microsecond timestamps: they are wall-anchored
# (epoch-scale, ~1.8e15 us), where a double's ULP is ~0.25 us — plus the
# 3-decimal rounding the export applies
EPS_US = 2.0


def spans_by_trace(doc: dict) -> dict:
    out: dict = {}
    for ev in doc["traceEvents"]:
        out.setdefault(ev["args"]["trace_id"], []).append(ev)
    return out


def assert_publish_tree(events: list) -> None:
    """The span-tree invariants for one trace's origin-worker events:
    exactly one root, every stage child parented on it, children
    back-to-back inside the root's window, ending where the root ends."""
    roots = [e for e in events if e["name"] == "publish"]
    assert len(roots) == 1
    root = roots[0]
    t0, t1 = root["ts"], root["ts"] + root["dur"]
    stages = sorted(
        (e for e in events if e["cat"] == "stage"), key=lambda e: e["ts"]
    )
    assert stages, "no stage children"
    prev_end = t0
    for ev in stages:
        assert ev["args"]["parent_id"] == root["args"]["span_id"]
        assert ev["ts"] >= t0 - EPS_US
        assert ev["ts"] + ev["dur"] <= t1 + EPS_US
        # stage spans tile the root: each begins where the last ended
        assert abs(ev["ts"] - prev_end) <= EPS_US, (ev["name"], ev["ts"], prev_end)
        prev_end = ev["ts"] + ev["dur"]
    assert abs(prev_end - t1) <= EPS_US  # the last stage closes the root


# -- tracer unit behavior ----------------------------------------------------


class TestTracer:
    def test_seeded_ids_are_deterministic(self):
        a, b = Tracer(seed=42), Tracer(seed=42)
        assert [a.new_trace_id() for _ in range(4)] == [
            b.new_trace_id() for _ in range(4)
        ]
        assert a.new_span_id() == b.new_span_id()

    def test_sampling_verdicts_and_ids_replay(self):
        """Two identically-seeded planes make identical sampling
        decisions AND identical trace ids — a repro run traces the same
        publishes under the same ids."""

        def drive():
            tele = Telemetry(sample=4)
            tele.attach_tracer(Tracer(seed=7, sample=4))
            out = []
            for i in range(16):
                c = tele.publish_clock()
                out.append((i, getattr(c, "trace_id", None)))
            return out

        assert drive() == drive()

    def test_trace_sampling_independent_of_stage_sampling(self):
        tele = Telemetry(sample=0)  # stage sampling off entirely
        tele.attach_tracer(Tracer(seed=1, sample=2))
        clocks = [tele.publish_clock() for _ in range(8)]
        traced = [c for c in clocks if c is not None]
        assert len(traced) == 4
        assert all(c.trace_id for c in traced)

    def test_ring_is_bounded(self):
        t = Tracer(ring=32, seed=0)
        for i in range(100):
            t.add_span(f"s{i}", "x", "t1", f"{i}", None, 0.0, 1e-6)
        assert len(t.ring) == 32
        assert t.spans_total == 100

    def test_finish_publish_emits_root_and_stage_children(self):
        t = Tracer(seed=3)
        tr = t.publish_trace()
        tr.stamp("decode")
        tr.stamp("admission")
        tr.stamp("fanout")
        t.finish_publish(tr, "a/b", 1)
        doc = t.export()
        assert check_trace_events(doc) == 4
        by_trace = spans_by_trace(doc)
        assert list(by_trace) == [tr.trace_id]
        assert_publish_tree(by_trace[tr.trace_id])
        root = [e for e in by_trace[tr.trace_id] if e["name"] == "publish"][0]
        assert root["args"]["topic"] == "a/b" and root["args"]["qos"] == 1

    @pytest.mark.parametrize("batch", [None, 41])
    def test_root_span_names_the_batch_that_carried_it(self, batch):
        """A staged publish's clock is stamped with its device batch's
        number (BatchProfile.seq): the root span's ``batch`` arg joins it
        to the mqtt/* annotations of a profiler trace. A publish that
        was never staged has no such arg; the children keep their shape."""
        t = Tracer(seed=5)
        tr = t.publish_trace()
        assert tr.batch is None
        tr.stamp("decode")
        tr.batch = batch
        tr.stamp("fanout")
        t.finish_publish(tr, "a/b", 0)
        events = spans_by_trace(t.export())[tr.trace_id]
        assert_publish_tree(events)
        root = [e for e in events if e["name"] == "publish"][0]
        assert root["args"].get("batch") == batch
        assert ("batch" in root["args"]) == (batch is not None)
        assert all("batch" not in e["args"] for e in events if e["cat"] == "stage")

    def test_adopted_weird_trace_ids_export_safely(self):
        t = Tracer(seed=0)
        tr = t.publish_trace("client-chose-this-id/πß")
        tr.stamp("fanout")
        t.finish_publish(tr, "t", 0)
        assert check_trace_events(t.export()) == 2


# -- the trace-event validator ----------------------------------------------


class TestValidator:
    def test_rejects_garbage(self):
        with pytest.raises(ValueError):
            check_trace_events({"traceEvents": []})
        with pytest.raises(ValueError):
            check_trace_events({"nope": 1})
        with pytest.raises(ValueError):
            check_trace_events('{"traceEvents": [{"ph": "X"}]}')  # no name
        ok = {
            "name": "s", "ph": "X", "ts": 1.0, "dur": 1.0,
            "pid": 0, "tid": 0, "args": {},
        }
        for bad in (
            {**ok, "ph": "B"},
            {**ok, "dur": -1},
            {**ok, "ts": "x"},
            {**ok, "pid": "0"},
            {**ok, "args": 7},
        ):
            with pytest.raises(ValueError):
                check_trace_events({"traceEvents": [bad]})

    def test_accepts_unresolved_parents(self):
        # one worker's half of a cross-worker trace is a legal export
        ev = {
            "name": "remote_fanout", "ph": "X", "ts": 1.0, "dur": 2.0,
            "pid": 1, "tid": 9,
            "args": {"trace_id": "t", "span_id": "a", "parent_id": "elsewhere"},
        }
        assert check_trace_events({"traceEvents": [ev]}) == 1

    def test_accepts_json_string(self):
        t = Tracer(seed=1)
        t.add_span("s", "c", "t1", "a", None, 0.0, 1e-6)
        assert check_trace_events(t.export_json()) == 1


# -- device duty-cycle profiler ---------------------------------------------


class TestDeviceProfiler:
    def test_window_union_overlap_and_idle_math(self):
        p = DeviceProfiler()
        # batch 1: dispatched at t=1, synced at t=3 -> window [1, 3]
        r1, r2, r3 = p.open_batch(), p.open_batch(), p.open_batch()
        p.note_dispatch(r1, 0.0, 1.0)
        # batch 2: dispatched at t=2 (overlaps batch 1), window [2, 4]
        p.note_dispatch(r2, 1.5, 2.0)
        p.note_resolve(r1, 2.5, 3.0)
        p.note_resolve(r2, 3.5, 4.0)
        # batch 3 after a 6s idle gap: window [10, 11]
        p.note_dispatch(r3, 9.0, 10.0)
        p.note_resolve(r3, 10.5, 11.0)
        assert p.batches == 3
        # busy union [1,4] + [10,11] = 4s over wall [1, 11] = 10s
        assert p.duty_cycle() == pytest.approx(0.4)
        # summed windows 2+2+1 = 5s; overlapped [2,3] = 1s
        assert p.overlap_ratio() == pytest.approx(0.2)
        assert p.idle_gap_hist.count == 1
        assert 6.0 <= p.idle_gap_hist.percentile(0.99) <= 10.0

    def test_record_pairing_is_exact_out_of_order(self):
        """Concurrent/out-of-order resolution (the resilience guard
        pool) cannot cross-attribute windows: each batch's boundaries
        live on its own record."""
        p = DeviceProfiler()
        a, b = p.open_batch(), p.open_batch()
        p.note_dispatch(a, 0.0, 1.0)
        p.note_dispatch(b, 1.0, 2.0)
        p.note_resolve(b, 2.0, 3.0)  # B resolves FIRST
        p.note_resolve(a, 4.0, 5.0)
        assert a.dispatch == (0.0, 1.0) and a.d2h == (4.0, 5.0)
        assert b.dispatch == (1.0, 2.0) and b.d2h == (2.0, 3.0)
        assert p.batches == 2

    def test_undispatched_record_stays_empty(self):
        # the exact-map fast path / host fallback never fill the record:
        # the staging drain then applies the coarse device_batch stamp
        p = DeviceProfiler()
        rec = p.open_batch()
        assert rec.dispatch is None and rec.d2h is None
        p.note_resolve(rec, 1.0, 2.0)  # resolve without dispatch
        assert p.batches == 0 and p.d2h_hist.count == 1
        assert p.duty_cycle() == 0.0


# -- staged broker end-to-end: span-tree integrity ---------------------------


class TestStagedSpanTree:
    def test_full_pipeline_span_tree_and_invariants(self):
        """Every sampled publish through the staged device pipeline
        yields one root with decode -> admission -> staging_wait -> h2d
        -> device_dispatch -> d2h -> encode -> flush children that tile
        the root window (the batched fan-out splits the old fanout span
        — ISSUE 13), and the export passes the validator."""

        async def scenario():
            h = Harness(
                Options(
                    inline_client=True,
                    device_matcher=True,
                    matcher_stage_window_ms=2.0,
                    matcher_opts={"max_levels": 4, "background": False},
                    telemetry_sample=1,
                    trace_sample=1,  # every publish carries a trace
                )
            )
            await h.server.serve()
            assert h.server.tracer is not None
            assert h.server.profiler is not None

            sub_r, sub_w, _ = await h.connect("sub")
            sub_w.write(sub_packet(1, [Subscription(filter="t/#", qos=0)]))
            await sub_w.drain()
            assert (await read_wire_packet(sub_r)).fixed_header.type == SUBACK
            h.server.matcher.flush()

            pub_r, pub_w, _ = await h.connect("pub")
            n = 12
            for i in range(n):
                pub_w.write(pub_packet(f"t/{i}", f"m{i}".encode()))
            await pub_w.drain()
            for _ in range(n):
                assert (await read_wire_packet(sub_r)).fixed_header.type == PUBLISH

            doc = h.server.tracer.export()
            assert check_trace_events(doc) > 0
            trees = spans_by_trace(doc)
            assert len(trees) == n
            expected = {
                "decode", "admission", "staging_wait",
                "h2d", "device_dispatch", "d2h", "encode", "flush",
            }
            for events in trees.values():
                assert_publish_tree(events)
                names = {e["name"] for e in events if e["cat"] == "stage"}
                assert names == expected, names
                # the root names the device batch that carried the publish
                root = [e for e in events if e["name"] == "publish"][0]
                assert isinstance(root["args"]["batch"], int)
            # the sub-stages also landed in the histograms, and
            # device_batch aggregates them exactly once per publish
            tele = h.server.telemetry
            for s in DEVICE_SUBSTAGES:
                assert tele.stage_hist[s].count == n
            assert tele.stage_hist["device_batch"].count == n
            # same continuity for the fan-out split: encode/flush land
            # in their own histograms AND the coarse fanout stage keeps
            # populating as their sum (exactly once per publish)
            for s in ("encode", "flush", "fanout"):
                assert tele.stage_hist[s].count == n, s

            await h.server.close()
            await h.shutdown()

        run(scenario())


# -- cross-worker trace join -------------------------------------------------


class TestMeshTraceJoin:
    def test_two_worker_join_packet_leg(self, tmp_path):
        """The acceptance drill: ONE sampled publish on a 2-worker mesh
        yields one joined trace — origin spans decode -> admission ->
        staging_wait -> h2d -> device_dispatch -> d2h -> fanout, a
        per-peer forward span, and the peer's remote_fanout span — and
        the merged export passes the in-repo validator."""

        async def scenario():
            h0 = Harness(
                Options(
                    inline_client=True,
                    device_matcher=True,
                    matcher_opts={"max_levels": 4, "background": False},
                    telemetry_sample=1,
                    trace_sample=1,
                )
            )
            h1 = Harness(
                Options(inline_client=True, telemetry_sample=1, trace_sample=1)
            )
            c0 = Cluster(h0.server, 0, 2, str(tmp_path))
            c1 = Cluster(h1.server, 1, 2, str(tmp_path))
            await h0.server.serve()
            await h1.server.serve()
            await c0.start()
            await c1.start()
            assert h0.server.tracer.pid == 0 and h1.server.tracer.pid == 1

            async def wait_for(cond, timeout=10.0):
                deadline = time.monotonic() + timeout
                while time.monotonic() < deadline:
                    if cond():
                        return True
                    await asyncio.sleep(0.02)
                return False

            assert await wait_for(
                lambda: c0.peer_count == 1 and c1.peer_count == 1
            )

            # a LOCAL wildcard subscriber on the origin keeps the filter
            # set non-exact, so the publish takes the packed device path
            # (h2d/device_dispatch/d2h); the REMOTE subscriber pulls the
            # forward leg
            l_r, l_w, _ = await h0.connect("local-sub")
            l_w.write(sub_packet(1, [Subscription(filter="tr/#", qos=0)]))
            await l_w.drain()
            assert (await read_wire_packet(l_r)).fixed_header.type == SUBACK
            r_r, r_w, _ = await h1.connect("remote-sub", version=5)
            r_w.write(
                sub_packet(1, [Subscription(filter="tr/t", qos=1)], version=5)
            )
            await r_w.drain()
            assert (await read_wire_packet(r_r, 5)).fixed_header.type == SUBACK
            assert await wait_for(
                lambda: c0._interested_peers("tr/t") == (1,)
            )
            h0.server.matcher.flush()

            p_r, p_w, _ = await h0.connect("pub", version=5)
            p_w.write(pub_packet("tr/t", b"joined", qos=1, pid=1, version=5))
            await p_w.drain()
            assert (await read_wire_packet(p_r, 5)).fixed_header.type == PUBACK
            got = await read_wire_packet(r_r, 5)
            assert got.fixed_header.type == PUBLISH
            assert bytes(got.payload) == b"joined"
            assert (await read_wire_packet(l_r)).fixed_header.type == PUBLISH
            assert await wait_for(
                lambda: any(s[0] == "remote_fanout" for s in h1.server.tracer.ring)
            )

            d0 = h0.server.tracer.export()
            d1 = h1.server.tracer.export()
            fwd = [e for e in d0["traceEvents"] if e["name"] == "forward"]
            assert len(fwd) == 1 and fwd[0]["args"]["peer"] == 1
            tid = fwd[0]["args"]["trace_id"]
            origin = [
                e for e in d0["traceEvents"] if e["args"]["trace_id"] == tid
            ]
            assert_publish_tree([e for e in origin if e["cat"] != "cluster"])
            names = {e["name"] for e in origin if e["cat"] == "stage"}
            assert names == {
                "decode", "admission", "staging_wait",
                "h2d", "device_dispatch", "d2h", "encode", "flush",
            }, names
            root = [e for e in origin if e["name"] == "publish"][0]
            assert fwd[0]["args"]["parent_id"] == root["args"]["span_id"]
            remote = [
                e for e in d1["traceEvents"] if e["name"] == "remote_fanout"
            ]
            assert len(remote) == 1
            assert remote[0]["args"]["trace_id"] == tid
            assert remote[0]["args"]["parent_id"] == fwd[0]["args"]["span_id"]
            assert remote[0]["pid"] == 1 and root["pid"] == 0
            # the merged two-worker document is ONE valid joined trace
            merged = {"traceEvents": d0["traceEvents"] + d1["traceEvents"]}
            assert check_trace_events(merged) == len(merged["traceEvents"])

            await c0.stop()
            await c1.stop()
            await h0.server.close()
            await h1.server.close()
            await h0.shutdown()
            await h1.shutdown()

        run(scenario())

    def test_traced_frame_leg_joins(self, tmp_path):
        """The QoS0 v4 passthrough leg: a traced frame forwards as
        _T_TFRAME and the peer's remote_fanout span joins the trace."""

        async def scenario():
            h0 = Harness(Options(inline_client=True, trace_sample=1))
            h1 = Harness(Options(inline_client=True, trace_sample=1))
            c0 = Cluster(h0.server, 0, 2, str(tmp_path))
            c1 = Cluster(h1.server, 1, 2, str(tmp_path))
            await h0.server.serve()
            await h1.server.serve()
            await c0.start()
            await c1.start()

            async def wait_for(cond, timeout=10.0):
                deadline = time.monotonic() + timeout
                while time.monotonic() < deadline:
                    if cond():
                        return True
                    await asyncio.sleep(0.02)
                return False

            assert await wait_for(
                lambda: c0.peer_count == 1 and c1.peer_count == 1
            )
            s_r, s_w, _ = await h1.connect("sub")
            s_w.write(sub_packet(1, [Subscription(filter="f/t", qos=0)]))
            await s_w.drain()
            assert (await read_wire_packet(s_r)).fixed_header.type == SUBACK
            assert await wait_for(lambda: c0._interested_peers("f/t") == (1,))

            # the raw v4 qos0 frame the fast path would relay verbatim
            topic = b"f/t"
            body = len(topic).to_bytes(2, "big") + topic + b"fastpath"
            frame = bytes([0x30, len(body)]) + body
            clock = h0.server.tracer.publish_trace()
            clock.stamp("decode")
            c0.forward_frame("f/t", frame, "pub", clock)
            got = await read_wire_packet(s_r)
            assert got.fixed_header.type == PUBLISH
            assert bytes(got.payload) == b"fastpath"
            assert await wait_for(
                lambda: any(s[0] == "remote_fanout" for s in h1.server.tracer.ring)
            )
            fwd = [
                e for e in h0.server.tracer.export()["traceEvents"]
                if e["name"] == "forward"
            ]
            assert len(fwd) == 1 and fwd[0]["args"]["sent"] is True
            remote = [
                e for e in h1.server.tracer.export()["traceEvents"]
                if e["name"] == "remote_fanout"
            ]
            assert remote[0]["args"]["trace_id"] == clock.trace_id
            assert remote[0]["args"]["parent_id"] == fwd[0]["args"]["span_id"]

            await c0.stop()
            await c1.stop()
            await h0.server.close()
            await h1.server.close()
            await h0.shutdown()
            await h1.shutdown()

        run(scenario())


# -- exemplars + flight-dump cross-link --------------------------------------


class TestExemplarDumpLink:
    def test_shed_dump_carries_trace_ids_and_sibling_trace_file(self, tmp_path):
        """A SHED dump's records name their trace ids, the snapshot
        dedupes them into trace_ids, a Perfetto-loadable traces_*.json
        lands beside the flight dump, and the /metrics exemplars point
        at the same ids."""
        srv = Server(
            Options(
                telemetry_sample=1,
                trace_sample=1,
                telemetry_dump_dir=str(tmp_path),
                overload_eval_interval_ms=0.001,
            )
        )
        tele = srv.telemetry
        ids = []
        for i in range(5):
            c = tele.publish_clock()
            assert c is not None and c.trace_id
            ids.append(c.trace_id)
            c.stamp("decode")
            c.stamp("fanout")
            tele.observe_publish(c, f"x/{i}", 0)
        srv.overload.add_source("test", lambda: 1.0)
        assert srv.overload.evaluate(force=True) == "shed"
        tele.recorder.join_writer()

        flights = sorted(tmp_path.glob("flight_*.json"))
        traces = sorted(tmp_path.glob("traces_*.json"))
        assert len(flights) == 1 and len(traces) == 1
        snap = json.load(open(flights[0]))
        assert snap["trace_ids"] == sorted(set(ids))
        assert all(r["trace_id"] in ids for r in snap["records"])
        doc = json.load(open(traces[0]))
        assert check_trace_events(doc) > 0
        dumped_ids = {e["args"]["trace_id"] for e in doc["traceEvents"]}
        assert set(ids) <= dumped_ids

        text = tele.exposition()
        assert check_exposition(text) > 0
        exemplar_lines = [l for l in text.splitlines() if "# {trace_id=" in l]
        assert exemplar_lines
        assert any(tid in l for tid in ids for l in exemplar_lines)

    def test_exemplars_disabled_by_knob(self):
        srv = Server(
            Options(telemetry_sample=1, trace_sample=1, trace_exemplars=False)
        )
        tele = srv.telemetry
        c = tele.publish_clock()
        c.stamp("fanout")
        tele.observe_publish(c, "t", 0)
        assert "# {trace_id=" not in tele.exposition()

    def test_checker_accepts_and_rejects_exemplar_forms(self):
        check_exposition(
            "# TYPE t_h histogram\n"
            't_h_bucket{le="0.1"} 3 # {trace_id="abc"} 0.05\n'
            't_h_bucket{le="+Inf"} 3\nt_h_sum 0.1\nt_h_count 3\n'
        )
        with pytest.raises(ValueError):
            check_exposition('t_h_bucket{le="0.1"} 3 # trace_id=abc\n')


# -- v5 user-property traces -------------------------------------------------


class TestUserPropertyTraces:
    def test_inbound_trace_id_is_adopted(self):
        """An inbound v5 publish carrying trace-id joins the broker's
        spans to the CLIENT-chosen id, even when sampling would have
        skipped it."""

        async def scenario():
            h = Harness(
                Options(
                    inline_client=True,
                    telemetry_sample=0,
                    trace_sample=1_000_000,  # natural sampling never fires
                )
            )
            await h.server.serve()
            s_r, s_w, _ = await h.connect("sub", version=5)
            s_w.write(sub_packet(1, [Subscription(filter="a/b", qos=0)], version=5))
            await s_w.drain()
            assert (await read_wire_packet(s_r, 5)).fixed_header.type == SUBACK
            p_r, p_w, _ = await h.connect("pub", version=5)
            props = Properties(user=[UserProperty(TRACE_USER_PROPERTY, "client-id-1")])
            p_w.write(pub_packet("a/b", b"x", version=5, props=props))
            await p_w.drain()
            got = await read_wire_packet(s_r, 5)
            assert got.fixed_header.type == PUBLISH
            doc = h.server.tracer.export()
            trees = spans_by_trace(doc)
            assert "client-id-1" in trees
            names = {e["name"] for e in trees["client-id-1"]}
            assert "publish" in names
            assert {"fanout"} <= names or {"encode", "flush"} <= names
            await h.server.close()
            await h.shutdown()

        run(scenario())

    def test_adoption_is_rate_bounded(self):
        """A client stamping trace-id on every publish cannot bypass
        trace_sample: adoptions cap at trace_adopt_max_per_s and the
        excess flows untraced."""
        from mqtt_tpu.telemetry import Telemetry

        tele = Telemetry(sample=0)
        tracer = Tracer(seed=1, sample=1_000_000)
        tracer.adopt_max_per_s = 3
        tele.attach_tracer(tracer)

        class _Pk:
            def __init__(self):
                self.properties = Properties(
                    user=[UserProperty(TRACE_USER_PROPERTY, "flood")]
                )

        adopted = sum(
            1
            for _ in range(10)
            if getattr(tele.adopt_trace(_Pk()), "trace_id", None) is not None
        )
        assert adopted == 3
        tracer.adopt_max_per_s = 0  # 0 disables adoption outright
        assert tele.adopt_trace(_Pk()) is None

    def test_outbound_stamp_behind_knob(self):
        """With trace_user_property on, a sampled publish's subscribers
        see the trace id as a v5 user property; default off."""

        async def scenario():
            h = Harness(
                Options(
                    inline_client=True,
                    telemetry_sample=1,
                    trace_sample=1,
                    trace_user_property=True,
                )
            )
            await h.server.serve()
            s_r, s_w, _ = await h.connect("sub", version=5)
            s_w.write(sub_packet(1, [Subscription(filter="a/b", qos=0)], version=5))
            await s_w.drain()
            assert (await read_wire_packet(s_r, 5)).fixed_header.type == SUBACK
            p_r, p_w, _ = await h.connect("pub", version=5)
            p_w.write(pub_packet("a/b", b"x", version=5))
            await p_w.drain()
            got = await read_wire_packet(s_r, 5)
            assert got.fixed_header.type == PUBLISH
            keys = {u.key: u.val for u in got.properties.user}
            assert TRACE_USER_PROPERTY in keys
            # the stamped id is the one the trace recorded
            trees = spans_by_trace(h.server.tracer.export())
            assert keys[TRACE_USER_PROPERTY] in trees
            await h.server.close()
            await h.shutdown()

        run(scenario())


# -- /traces HTTP matrix -----------------------------------------------------


async def _http(host, port, path, method="GET"):
    reader, writer = await asyncio.open_connection(host, int(port))
    writer.write(f"{method} {path} HTTP/1.1\r\nHost: x\r\n\r\n".encode())
    await writer.drain()
    raw = b""
    while True:
        try:
            chunk = await asyncio.wait_for(reader.read(65536), 3)
        except asyncio.TimeoutError:
            break
        if not chunk:
            break
        raw += chunk
    writer.close()
    return raw


class TestTracesEndpoint:
    def test_traces_matrix(self):
        async def scenario():
            h = Harness(Options(telemetry_sample=1, trace_sample=1))
            tele = h.server.telemetry
            c = tele.publish_clock()
            c.stamp("decode")
            c.stamp("fanout")
            tele.observe_publish(c, "t/x", 0)
            st = HTTPStats(
                LConfig(type="sysinfo", id="s", address="127.0.0.1:0"),
                h.server.info,
                telemetry=tele,
            )
            await st.init(h.server.log)
            host, port = st.address().rsplit(":", 1)
            data = await _http(host, port, "/traces")
            head, body = data.split(b"\r\n\r\n", 1)
            assert head.startswith(b"HTTP/1.1 200")
            assert b"application/json" in head
            assert b"Cache-Control: no-store" in head
            assert check_trace_events(body.decode()) > 0
            post = await _http(host, port, "/traces", "POST")
            assert post.startswith(b"HTTP/1.1 405") and b"Allow: GET" in post
            await st.close(lambda _: None)
            await h.shutdown()

        run(scenario())

    def test_traces_404_when_tracing_off(self):
        async def scenario():
            h = Harness(Options(telemetry_sample=1, trace=False))
            assert h.server.tracer is None
            st = HTTPStats(
                LConfig(type="sysinfo", id="s", address="127.0.0.1:0"),
                h.server.info,
                telemetry=h.server.telemetry,
            )
            await st.init(h.server.log)
            host, port = st.address().rsplit(":", 1)
            assert (await _http(host, port, "/traces")).startswith(
                b"HTTP/1.1 404"
            )
            # /metrics keeps working without the trace plane
            assert (await _http(host, port, "/metrics")).startswith(
                b"HTTP/1.1 200"
            )
            await st.close(lambda _: None)
            await h.shutdown()

        run(scenario())
