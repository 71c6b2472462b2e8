"""The unified telemetry plane (mqtt_tpu.telemetry): histogram bucket
math, Prometheus exposition format, the per-publish stage clock through a
real staged broker, the flight recorder's degradation triggers, the HTTP
surfaces (/metrics, 405-on-known-paths, Cache-Control), and the
monotonic-uptime drift fix.
"""

import asyncio
import json
import os

import pytest

from mqtt_tpu import Options, Server
from mqtt_tpu.listeners import Config as LConfig, HTTPHealthCheck, HTTPStats
from mqtt_tpu.packets import PUBLISH, SUBACK, Subscription
from mqtt_tpu.system import Info
from mqtt_tpu.telemetry import (
    FILL_BOUNDS,
    PUBLISH_STAGES,
    FlightRecorder,
    Histogram,
    MetricsRegistry,
    StageClock,
    Telemetry,
    check_exposition,
    escape_label_value,
)
from mqtt_tpu.topics import SYS_PREFIX

from tests.test_server import (
    Harness,
    ObservingHook,
    pub_packet,
    read_wire_packet,
    run,
    sub_packet,
)


# -- histogram bucket math ---------------------------------------------------


class TestHistogram:
    def test_log_scale_boundaries(self):
        h = Histogram(base=1e-6, growth=2.0, n_buckets=36)
        assert h.bounds[0] == 1e-6
        for a, b in zip(h.bounds, h.bounds[1:]):
            assert b / a == pytest.approx(2.0)
        # +Inf overflow bucket on top of the finite bounds
        assert len(h.counts) == len(h.bounds) + 1

    def test_boundary_observation_is_le(self):
        """A value exactly on a bucket boundary counts in THAT bucket
        (Prometheus `le` semantics)."""
        h = Histogram(base=1e-6, growth=2.0, n_buckets=8)
        h.observe(h.bounds[3])
        assert h.counts[3] == 1 and sum(h.counts) == 1
        h.observe(h.bounds[3] * 1.0001)  # just past: next bucket
        assert h.counts[4] == 1

    def test_underflow_and_overflow(self):
        h = Histogram(base=1e-6, growth=2.0, n_buckets=4)
        h.observe(0.0)  # below the base: first bucket
        assert h.counts[0] == 1
        h.observe(1e9)  # past the last bound: +Inf bucket
        assert h.counts[-1] == 1
        assert h.count == 2

    def test_percentile_edge_counts(self):
        h = Histogram(base=1e-6, growth=2.0, n_buckets=16)
        assert h.percentile(0.99) == 0.0  # empty
        h.observe(3e-6)  # lands in the (2us, 4us] bucket
        # a single observation answers every quantile with its bucket
        for q in (0.01, 0.5, 0.99, 1.0):
            assert h.percentile(q) == pytest.approx(4e-6)
        # overflow observations report the largest finite bound
        h2 = Histogram(base=1e-6, growth=2.0, n_buckets=4)
        h2.observe(1e9)
        assert h2.percentile(0.99) == h2.bounds[-1]

    def test_percentile_rank_math(self):
        h = Histogram(base=1e-6, growth=2.0, n_buckets=16)
        for _ in range(99):
            h.observe(3e-6)  # -> 4us bucket
        h.observe(1e-3)  # one outlier -> ~1ms bucket
        assert h.percentile(0.50) == pytest.approx(4e-6)
        # p99 rank = ceil(0.99*100) = 99 -> still the 4us bucket
        assert h.percentile(0.99) == pytest.approx(4e-6)
        assert h.percentile(1.0) >= 1e-3

    def test_merge_of_shards(self):
        """Per-thread shards merge into one aggregate (same layout)."""
        a, b = Histogram(n_buckets=8), Histogram(n_buckets=8)
        for v in (1e-6, 5e-6, 9e-6):
            a.observe(v)
        for v in (2e-5, 3e-5):
            b.observe(v)
        a.merge(b)
        assert a.count == 5
        assert a.sum == pytest.approx(1e-6 + 5e-6 + 9e-6 + 2e-5 + 3e-5)
        assert sum(a.counts) == 5

    def test_merge_layout_mismatch_raises(self):
        a = Histogram(n_buckets=8)
        b = Histogram(n_buckets=9)
        with pytest.raises(ValueError):
            a.merge(b)

    def test_callback_histogram_renders_merged_snapshot(self):
        """A registry histogram may be backed by a scrape-time callback
        returning a merged snapshot (the sharded matcher's per-thread
        shard pattern): exposition and sys_tree render the snapshot, and
        a failing callback degrades to the empty stored child instead of
        killing the scrape."""
        from mqtt_tpu.telemetry import MetricsRegistry, check_exposition

        shards = [Histogram(), Histogram()]
        shards[0].observe(1e-5)
        shards[1].observe(2e-5)
        shards[1].observe(4e-5)

        def merged():
            out = Histogram()
            for s in shards:
                out.merge(s)
            return out

        r = MetricsRegistry()
        r.histogram("mqtt_tpu_shardy_seconds", "merged shards", fn=merged)
        text = r.exposition()
        check_exposition(text)
        assert "mqtt_tpu_shardy_seconds_count 3" in text
        tree = r.sys_tree()
        assert tree["shardy_seconds/count"] == 3
        shards[0].observe(8e-5)  # live: the next scrape sees new data
        assert r.sys_tree()["shardy_seconds/count"] == 4

        def boom():
            raise RuntimeError("shard walk failed")

        r2 = MetricsRegistry()
        r2.histogram("mqtt_tpu_shardy_seconds", "merged shards", fn=boom)
        assert "mqtt_tpu_shardy_seconds_count 0" in r2.exposition()

    def test_linear_bounds_for_ratios(self):
        h = Histogram(bounds=FILL_BOUNDS)
        h.observe(0.05)
        h.observe(0.55)
        h.observe(1.0)
        assert h.counts[0] == 1  # <= 0.1
        assert h.counts[5] == 1  # <= 0.6
        assert h.counts[9] == 1  # exactly 1.0 -> last finite bucket
        assert h.counts[-1] == 0


# -- exposition format -------------------------------------------------------


class TestExposition:
    def test_help_type_and_samples(self):
        r = MetricsRegistry()
        r.counter("t_requests_total", "Total requests").inc(3)
        r.gauge("t_depth", "Queue depth").set(7)
        h = r.histogram("t_latency_seconds", "Latency")
        h.observe(3e-6)
        text = r.exposition()
        lines = text.splitlines()
        assert "# HELP t_requests_total Total requests" in lines
        assert "# TYPE t_requests_total counter" in lines
        assert "# TYPE t_depth gauge" in lines
        assert "# TYPE t_latency_seconds histogram" in lines
        # one TYPE line per family, even with many children
        assert sum(1 for l in lines if l.startswith("# TYPE ")) == 3
        assert "t_requests_total 3" in lines
        assert "t_depth 7" in lines
        # histogram renders cumulative buckets + sum + count
        assert any(l.startswith("t_latency_seconds_bucket{le=") for l in lines)
        assert 't_latency_seconds_bucket{le="+Inf"} 1' in lines
        assert "t_latency_seconds_count 1" in lines
        assert check_exposition(text) > 0

    def test_histogram_buckets_cumulative(self):
        r = MetricsRegistry()
        h = r.histogram("t_h", "x")
        for v in (1e-6, 1e-6, 1e-3, 10.0):
            h.observe(v)
        lines = [
            l for l in r.exposition().splitlines() if l.startswith("t_h_bucket")
        ]
        counts = [int(l.rsplit(" ", 1)[1]) for l in lines]
        assert counts == sorted(counts), "bucket counts must be cumulative"
        assert counts[-1] == 4  # +Inf == total count

    def test_label_escaping(self):
        assert escape_label_value('a"b\\c\nd') == 'a\\"b\\\\c\\nd'
        r = MetricsRegistry()
        r.counter("t_labeled_total", "labels", topic='we/"ird\\\n').inc()
        text = r.exposition()
        assert '\\"ird\\\\\\n' in text
        assert check_exposition(text) > 0  # the checker accepts escapes

    def test_checker_rejects_garbage(self):
        with pytest.raises(ValueError):
            check_exposition("this is not a metric line\n")
        with pytest.raises(ValueError):
            check_exposition("# TYPE foo frobnicator\nfoo 1\n")
        with pytest.raises(ValueError):
            check_exposition("")  # no samples

    def test_type_conflict_rejected(self):
        r = MetricsRegistry()
        r.counter("t_x", "a")
        with pytest.raises(ValueError):
            r.gauge("t_x", "b")
        with pytest.raises(ValueError):
            r.counter("bad name!", "c")

    def test_sys_tree(self):
        r = MetricsRegistry()
        r.counter("mqtt_tpu_foo_total", "x").inc(2)
        h = r.histogram("mqtt_tpu_lat_seconds", "x", stage="decode")
        h.observe(2e-3)
        fill = r.histogram("mqtt_tpu_fill_ratio", "x", bounds=FILL_BOUNDS)
        fill.observe(0.7)
        tree = r.sys_tree()
        assert tree["foo_total"] == 2
        assert tree["lat_seconds/decode/count"] == 1
        assert tree["lat_seconds/decode/p99_ms"] >= 2.0
        # dimensionless histograms surface RAW quantiles, never *_ms
        assert tree["fill_ratio/p50"] == pytest.approx(0.7)
        assert "fill_ratio/p50_ms" not in tree


# -- stage clock / sampling --------------------------------------------------


class TestStageClockAndSampling:
    def test_stage_durations_sum_to_total(self):
        c = StageClock()
        c.stamp("decode")
        c.stamp("admission")
        c.stamp("fanout")
        assert [s for s, _ in c.stages] == ["decode", "admission", "fanout"]
        assert sum(dt for _, dt in c.stages) == pytest.approx(c.total())

    def test_one_in_n_sampling(self):
        t = Telemetry(sample=4)
        clocks = [t.publish_clock() for _ in range(12)]
        assert sum(1 for c in clocks if c is not None) == 3
        assert clocks[3] is not None and clocks[0] is None

    def test_sampling_disabled(self):
        t = Telemetry(sample=0)
        assert all(t.publish_clock() is None for _ in range(10))
        assert not any(t.sample_outbound() for _ in range(10))

    def test_observe_publish_feeds_histograms_and_ring(self):
        t = Telemetry(sample=1, ring=4)
        for i in range(6):
            c = t.publish_clock()
            c.stamp("decode")
            c.stamp("fanout")
            t.observe_publish(c, topic=f"a/{i}", qos=0)
        assert t.stage_hist["decode"].count == 6
        assert t.stage_hist["fanout"].count == 6
        assert len(t.recorder.ring) == 4  # ring bounded
        rec = list(t.recorder.ring)[-1]
        assert rec["topic"] == "a/5" and "decode" in rec["stages_ms"]


# -- flight recorder ---------------------------------------------------------


class TestFlightRecorder:
    def test_dump_and_rate_limit(self, tmp_path):
        fr = FlightRecorder(size=8, dump_dir=str(tmp_path), min_interval_s=60.0)
        for i in range(3):
            fr.add({"t": i})
        path = fr.dump("test_reason", {"k": "v"})
        assert path is not None and os.path.exists(path)
        snap = json.load(open(path))
        assert snap["reason"] == "test_reason"
        assert snap["context"] == {"k": "v"}
        assert [r["t"] for r in snap["records"]] == [0, 1, 2]
        # second dump inside the interval is suppressed
        assert fr.dump("again") is None
        assert fr.dumps == 1 and fr.dumps_suppressed == 1

    def test_dump_async_offloads_io(self, tmp_path):
        fr = FlightRecorder(size=8, dump_dir=str(tmp_path), min_interval_s=0.0)
        fr.add({"t": 1})
        fr.dump_async("async_reason")
        fr.join_writer()
        files = list(tmp_path.iterdir())
        assert len(files) == 1 and "async_reason" in files[0].name

    def test_add_during_dump_is_safe(self, tmp_path):
        """add() and dump() race from different threads without losing
        the dump to a 'deque mutated during iteration'."""
        import threading

        fr = FlightRecorder(size=512, dump_dir=str(tmp_path), min_interval_s=0.0)
        stop = threading.Event()

        def pound():
            i = 0
            while not stop.is_set():
                fr.add({"t": i})
                i += 1

        th = threading.Thread(target=pound, daemon=True)
        th.start()
        try:
            for i in range(20):
                assert fr.dump(f"race_{i}") is not None
        finally:
            stop.set()
            th.join(2)
        assert fr.dumps == 20

    def test_shed_transition_dumps(self, tmp_path):
        """A NORMAL -> SHED transition in the governor dumps the ring
        (the server wires on_transition in __init__)."""
        srv = Server(
            Options(
                telemetry_sample=1,
                telemetry_dump_dir=str(tmp_path),
                overload_eval_interval_ms=0.001,
            )
        )
        srv.overload.add_source("test", lambda: 1.0)
        srv.telemetry.recorder.add({"t": 1})
        state = srv.overload.evaluate(force=True)
        assert state == "shed"
        srv.telemetry.recorder.join_writer()  # dump IO is off-thread
        flights = sorted(tmp_path.glob("flight_*.json"))
        assert len(flights) == 1 and "overload_shed" in flights[0].name
        snap = json.load(open(flights[0]))
        assert snap["context"]["to"] == "shed"
        assert snap["context"]["gauges"]["state"] == "shed"
        # the trace plane (on by default) writes its sibling export on
        # the same writer thread (mqtt_tpu.tracing)
        traces = sorted(tmp_path.glob("traces_*.json"))
        assert len(traces) == 1 and "overload_shed" in traces[0].name

    def test_breaker_trip_dumps(self, tmp_path):
        """A matcher breaker trip dumps the ring (server chains the
        breaker's on_trip)."""
        srv = Server(
            Options(
                device_matcher=True,
                matcher_opts={"max_levels": 4, "background": False},
                breaker_failure_threshold=2,
                telemetry_dump_dir=str(tmp_path),
            )
        )
        try:
            breaker = srv.matcher.breaker
            breaker.record_failure("error")
            breaker.record_failure("error")
            assert breaker.trips == 1
            srv.telemetry.recorder.join_writer()  # dump IO is off-thread
            flights = sorted(tmp_path.glob("flight_*.json"))
            assert len(flights) == 1 and "breaker_trip" in flights[0].name
            # the trace plane's sibling export rides the same trigger
            assert len(sorted(tmp_path.glob("traces_*.json"))) == 1
        finally:
            srv.matcher.close()


# -- cluster link RTT --------------------------------------------------------


class TestClusterRtt:
    def test_pong_observes_rtt_histogram(self, tmp_path):
        import struct
        import time as _time

        from mqtt_tpu.cluster import Cluster

        srv = Server(Options(telemetry_sample=1))
        c = Cluster(srv, worker_id=0, n_workers=2, sock_dir=str(tmp_path))
        c._on_pong(1, struct.pack(">d", _time.perf_counter() - 0.005))
        h = srv.telemetry.registry.histogram(
            "mqtt_tpu_cluster_peer_rtt_seconds", peer="1"
        )
        assert h.count == 1 and h.sum >= 0.005
        c._on_pong(1, b"short")  # malformed payloads are ignored
        c._on_pong(1, struct.pack(">d", _time.perf_counter() + 100))  # anomaly
        assert h.count == 1
        text = srv.telemetry.exposition()
        assert 'mqtt_tpu_cluster_peer_rtt_seconds_bucket{peer="1"' in text
        assert check_exposition(text) > 0


# -- monotonic uptime (satellite) -------------------------------------------


class TestUptimeDrift:
    def test_uptime_survives_wall_clock_steps(self):
        info = Info(version="x", started=1_000_000)
        info._mono_started -= 7  # 7s of real elapsed time
        info.started += 3600  # wall clock stepped an hour FORWARD
        assert info.as_dict()["uptime"] == 7
        info.started -= 7200  # ...and back two hours
        assert info.uptime_now() == 7

    def test_clone_keeps_anchor_and_asdict_excludes_it(self):
        info = Info()
        info._mono_started -= 5
        c = info.clone()
        assert c.uptime_now() >= 5
        assert "_mono_started" not in c.as_dict()

    def test_sys_uptime_uses_monotonic(self):
        async def scenario():
            h = Harness()
            h.server.info._mono_started -= 9
            h.server.info.started += 10_000  # wall step must not matter
            h.server.publish_sys_topics()
            msgs = {
                p.topic_name: p for p in h.server.topics.messages("$SYS/#")
            }
            assert 9 <= int(bytes(msgs["$SYS/broker/uptime"].payload)) < 60
            await h.shutdown()

        run(scenario())


# -- HTTP surfaces -----------------------------------------------------------


async def _http(host, port, path, method="GET"):
    reader, writer = await asyncio.open_connection(host, int(port))
    writer.write(f"{method} {path} HTTP/1.1\r\nHost: x\r\n\r\n".encode())
    await writer.drain()
    # the listener answers Connection: close — read to EOF, not one
    # recv (a grown /metrics body spans several TCP segments)
    chunks = []
    while True:
        chunk = await asyncio.wait_for(reader.read(65536), 3)
        if not chunk:
            break
        chunks.append(chunk)
    writer.close()
    return b"".join(chunks)


class TestHttpSurfaces:
    def test_healthcheck_method_matrix(self):
        async def scenario():
            hc = HTTPHealthCheck(
                LConfig(type="healthcheck", id="h", address="127.0.0.1:0")
            )
            await hc.init(__import__("logging").getLogger("t"))
            host, port = hc.address().rsplit(":", 1)
            ok = await _http(host, port, "/healthcheck")
            assert ok.startswith(b"HTTP/1.1 200")
            # non-GET on a KNOWN path: 405 with Allow
            post = await _http(host, port, "/healthcheck", "POST")
            assert post.startswith(b"HTTP/1.1 405") and b"Allow: GET" in post
            # unknown path: 404 regardless of method
            assert (await _http(host, port, "/nope")).startswith(b"HTTP/1.1 404")
            assert (await _http(host, port, "/nope", "POST")).startswith(
                b"HTTP/1.1 404"
            )
            await hc.close(lambda _: None)

        run(scenario())

    def test_stats_no_store_and_405(self):
        async def scenario():
            h = Harness()
            st = HTTPStats(
                LConfig(type="sysinfo", id="s", address="127.0.0.1:0"),
                h.server.info,
            )
            await st.init(h.server.log)
            host, port = st.address().rsplit(":", 1)
            data = await _http(host, port, "/")
            assert data.startswith(b"HTTP/1.1 200")
            assert b"Cache-Control: no-store" in data
            post = await _http(host, port, "/", "POST")
            assert post.startswith(b"HTTP/1.1 405") and b"Allow: GET" in post
            # no telemetry attached: /metrics is an unknown path
            assert (await _http(host, port, "/metrics")).startswith(
                b"HTTP/1.1 404"
            )
            await st.close(lambda _: None)
            await h.shutdown()

        run(scenario())

    def test_dashboard_unknown_path_404_on_post(self):
        from mqtt_tpu.listeners import Dashboard

        async def scenario():
            h = Harness()
            d = Dashboard(
                LConfig(type="dashboard", id="d", address="127.0.0.1:0"),
                h.server.info,
                h.server.clients,
            )
            await d.init(h.server.log)
            host, port = d.address().rsplit(":", 1)
            info = await _http(host, port, "/information")
            assert info.startswith(b"HTTP/1.1 200")
            assert b"Cache-Control: no-store" in info
            post = await _http(host, port, "/information", "POST")
            assert post.startswith(b"HTTP/1.1 405")
            assert (await _http(host, port, "/nope", "POST")).startswith(
                b"HTTP/1.1 404"
            )
            await d.close(lambda _: None)
            await h.shutdown()

        run(scenario())

    def test_metrics_endpoint_serves_exposition(self):
        async def scenario():
            h = Harness(Options(telemetry_sample=1))
            st = HTTPStats(
                LConfig(type="sysinfo", id="s", address="127.0.0.1:0"),
                h.server.info,
                telemetry=h.server.telemetry,
            )
            await st.init(h.server.log)
            host, port = st.address().rsplit(":", 1)
            data = await _http(host, port, "/metrics")
            head, body = data.split(b"\r\n\r\n", 1)
            assert head.startswith(b"HTTP/1.1 200")
            assert b"text/plain; version=0.0.4" in head
            assert b"Cache-Control: no-store" in head
            text = body.decode()
            assert check_exposition(text) > 0
            assert "mqtt_tpu_publish_stage_seconds" in text
            assert "mqtt_tpu_uptime_seconds" in text
            await st.close(lambda _: None)
            await h.shutdown()

        run(scenario())


# -- staged broker end-to-end ------------------------------------------------


class TestStagedPipelineTelemetry:
    def test_stage_histograms_sys_tree_and_metrics(self):
        """Every pipeline stage records through a real staged broker:
        decode -> admission -> staging_wait -> device_batch -> fanout,
        batch service/fill histograms, and both exposition surfaces."""

        async def scenario():
            h = Harness(
                Options(
                    inline_client=True,
                    device_matcher=True,
                    matcher_stage_window_ms=2.0,
                    matcher_opts={"max_levels": 4, "background": False},
                    telemetry_sample=1,  # every publish carries a clock
                )
            )
            await h.server.serve()
            tele = h.server.telemetry
            assert tele is not None and h.server._stage.telemetry is tele

            sub_r, sub_w, _ = await h.connect("sub")
            sub_w.write(sub_packet(1, [Subscription(filter="t/#", qos=0)]))
            await sub_w.drain()
            assert (await read_wire_packet(sub_r)).fixed_header.type == SUBACK
            h.server.matcher.flush()

            pub_r, pub_w, _ = await h.connect("pub")
            n = 24
            for i in range(n):
                pub_w.write(pub_packet(f"t/{i}", f"m{i}".encode()))
            await pub_w.drain()
            for _ in range(n):
                pk = await read_wire_packet(sub_r)
                assert pk.fixed_header.type == PUBLISH

            # every stage of the staged pipeline observed samples
            for stage in PUBLISH_STAGES:
                assert tele.stage_hist[stage].count > 0, stage
            assert tele.batch_service.count > 0
            assert tele.batch_fill.count > 0
            # NOTE: outbound_wait deliberately unasserted here — the
            # batched fan-out (ISSUE 13) delivers to idle sockets
            # directly, so nothing queues and there is no queue wait to
            # observe; the queued path's sampling is covered by
            # test_outbound_queue_wait_sampling
            assert tele.sampled_publishes.value >= n

            # $SYS tree surfaces the same aggregates
            h.server.publish_sys_topics()
            retained = h.server.topics.retained
            for stage in PUBLISH_STAGES:
                t = SYS_PREFIX + f"/broker/telemetry/publish_stage_seconds/{stage}/p99_ms"
                assert retained.get(t) is not None, t
            assert (
                retained.get(SYS_PREFIX + "/broker/telemetry/flight/ring_depth")
                is not None
            )

            # the exposition parses and carries the acceptance metrics
            text = tele.exposition()
            assert check_exposition(text) > 0
            for stage in PUBLISH_STAGES:
                assert f'stage="{stage}"' in text
            assert "mqtt_tpu_stage_batch_fill_ratio_bucket" in text
            assert "mqtt_tpu_matcher_batches_total" in text

            await h.server.close()
            await h.shutdown()

        run(scenario())

    def test_outbound_queue_wait_sampling(self):
        """The per-subscriber fan-out (a hook observes sends) delivers
        through the bounded outbound queue, so sampled enqueues observe
        a queue wait — the path the batched flush deliberately skips
        for idle sockets (ISSUE 13)."""

        async def scenario():
            h = Harness(Options(inline_client=True, telemetry_sample=1))
            h.server.add_hook(ObservingHook())
            await h.server.serve()
            tele = h.server.telemetry
            sub_r, sub_w, _ = await h.connect("sub")
            sub_w.write(sub_packet(1, [Subscription(filter="t/#", qos=0)]))
            await sub_w.drain()
            assert (await read_wire_packet(sub_r)).fixed_header.type == SUBACK
            pub_r, pub_w, _ = await h.connect("pub")
            for i in range(8):
                pub_w.write(pub_packet(f"t/{i}", b"m"))
            await pub_w.drain()
            for _ in range(8):
                pk = await read_wire_packet(sub_r)
                assert pk.fixed_header.type == PUBLISH
            assert tele.outbound_wait.count > 0

            await h.server.close()
            await h.shutdown()

        run(scenario())

    def test_disabled_telemetry_is_inert(self):
        async def scenario():
            h = Harness(Options(telemetry=False))
            await h.server.serve()
            assert h.server.telemetry is None
            r, w, _ = await h.connect("p")
            w.write(pub_packet("a/b", b"x"))
            await w.drain()
            h.server.publish_sys_topics()
            assert (
                h.server.topics.retained.get(
                    SYS_PREFIX + "/broker/telemetry/flight/ring_depth"
                )
                is None
            )
            await h.server.close()
            await h.shutdown()

        run(scenario())


class TestIngestRunCounts:
    def test_slice_counters_metrics_and_sys(self):
        """The runs and the publishes they took in ride the profiler
        slice's snapshots, /metrics and $SYS, and count what the read
        loop handed to ``ingest_run`` and nothing else: a retained
        publish and a SUBSCRIBE take the per-frame path."""

        async def scenario():
            h = Harness(
                Options(
                    inline_client=True, device_matcher=True,
                    matcher_stage_window_ms=2.0,
                    matcher_opts={"max_levels": 4, "background": False},
                )
            )
            srv = h.server
            await srv.serve()
            counts = srv._slice_counters()
            assert counts["ingest_runs"] == counts["ingest_run_publishes"] == 0
            sub_r, sub_w, _ = await h.connect("sub")
            sub_w.write(sub_packet(1, [Subscription(filter="t/#", qos=0)]))
            assert (await read_wire_packet(sub_r)).fixed_header.type == SUBACK
            srv.matcher.flush()
            _r, w, _ = await h.connect("pub")
            w.write(
                b"".join(pub_packet(f"t/{i}", b"m") for i in range(5))
                + pub_packet("t/kept", b"m", retain=True)
                + b"".join(pub_packet(f"t/{i}", b"m") for i in range(5, 8))
            )
            for _ in range(9):
                assert (await read_wire_packet(sub_r)).fixed_header.type == PUBLISH
            counts = srv._slice_counters()
            assert counts["ingest_run_publishes"] == 8
            # both connections sit on socketpairs: the direct feeder's
            assert counts["direct_reads"] == counts["socket_reads"] > 0
            # one run before the retained frame, one after it, unless
            # the socket handed the bytes over in more reads
            assert 2 <= counts["ingest_runs"] <= 8
            assert srv.info.messages_received == 9
            text = srv.telemetry.registry.exposition()
            assert "mqtt_tpu_ingest_run_publishes_total 8" in text
            assert f"mqtt_tpu_ingest_runs_total {counts['ingest_runs']}" in text
            srv.publish_sys_topics()
            got = {
                p.topic_name: bytes(p.payload)
                for p in srv.topics.messages("$SYS/broker/ingest/#")
            }
            assert got == {
                "$SYS/broker/ingest/run_publishes": b"8",
                "$SYS/broker/ingest/runs": str(counts["ingest_runs"]).encode(),
                # no PUBACK came in (tests/test_ack_run.py counts them)
                "$SYS/broker/ingest/ack_runs": b"0",
                "$SYS/broker/ingest/ack_run_acks": b"0",
                "$SYS/broker/ingest/direct_reads": str(
                    counts["direct_reads"]
                ).encode(),
            }
            await srv.close()
            await h.shutdown()

        run(scenario())
