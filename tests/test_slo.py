"""The cluster-wide SLO observatory's engine half (ISSUE 14,
mqtt_tpu.slo + the delivery-latency SLI in mqtt_tpu.telemetry): the
objective grammar, burn-rate window math against injected clocks, the
seeded latency-injection breach end-to-end (retained $SYS transition +
gauges + flight dump), the delivery SLI's local/remote stamping through
a real broker, and the /healthz readiness surface's 200/503 + method
matrix.
"""

import asyncio
import json
import random

import pytest

from mqtt_tpu import Options, Server
from mqtt_tpu.listeners import Config as LConfig, HTTPStats
from mqtt_tpu.packets import PUBLISH, Subscription
from mqtt_tpu.slo import (
    ObjectiveError,
    SLOEngine,
    parse_objective,
    parse_objectives,
)
from mqtt_tpu.telemetry import Histogram, RemoteStageClock, Telemetry

from tests.test_server import (
    Harness,
    pub_packet,
    read_wire_packet,
    run,
    sub_packet,
)
from tests.test_telemetry import _http


# -- objective grammar -------------------------------------------------------


class TestObjectiveGrammar:
    def test_latency_objective(self):
        o = parse_objective("p99 delivery < 50ms over 5m")
        assert o.kind == "latency"
        assert o.family == "mqtt_tpu_delivery_latency_seconds"
        assert o.threshold_s == pytest.approx(0.05)
        assert o.budget == pytest.approx(0.01)
        assert (o.fast_s, o.slow_s) == (300.0, 3600.0)

    def test_latency_label_filter_and_explicit_windows(self):
        o = parse_objective("p95 delivery{tenant=acme,qos=1} < 20ms over 30s/2m")
        assert o.labels == {"tenant": "acme", "qos": "1"}
        assert o.budget == pytest.approx(0.05)
        assert (o.fast_s, o.slow_s) == (30.0, 120.0)

    def test_slow_window_floored_at_fast(self):
        o = parse_objective("p99 delivery < 50ms over 10m/1m")
        assert o.slow_s == o.fast_s

    def test_named_ratio(self):
        o = parse_objective("shed ratio < 0.1%")
        assert o.kind == "ratio"
        assert o.numerator == "mqtt_tpu_messages_dropped_total"
        assert o.denominator == "mqtt_tpu_messages_received_total"
        assert o.budget == pytest.approx(0.001)

    def test_explicit_family_ratio(self):
        o = parse_objective(
            "messages_dropped_total/messages_received_total ratio < 2% "
            "over 1m"
        )
        assert o.numerator == "mqtt_tpu_messages_dropped_total"
        assert o.denominator == "mqtt_tpu_messages_received_total"
        assert o.budget == pytest.approx(0.02)
        assert o.fast_s == 60.0

    def test_explicit_histogram_family(self):
        o = parse_objective("p99 publish_stage_seconds < 5ms over 1m")
        assert o.family == "mqtt_tpu_publish_stage_seconds"

    @pytest.mark.parametrize(
        "bad",
        [
            "p99 delivery > 50ms",  # wrong comparator
            "p0 delivery < 50ms",  # quantile out of range
            "delivery < 50ms",  # no quantile
            "wat ratio < 1x",  # bad unit
            "unknown_sli ratio < 1%",  # unknown named ratio
            "p99 delivery < 50ms over fortnight",  # bad duration
            "",
        ],
    )
    def test_bad_specs_raise(self, bad):
        with pytest.raises(ObjectiveError):
            parse_objective(bad)

    def test_parse_objectives_skips_bad_lines_and_dedupes(self):
        objs = parse_objectives(
            [
                "p99 delivery < 50ms over 5m",
                "complete nonsense",
                "p99 delivery < 50ms over 5m",  # duplicate name
            ]
        )
        assert len(objs) == 2
        assert objs[0].name != objs[1].name


# -- histogram threshold math ------------------------------------------------


class TestCountLe:
    def test_count_le_on_and_off_bucket(self):
        h = Histogram(bounds=(0.001, 0.01, 0.1))
        for v in (0.0005, 0.005, 0.05, 0.5):
            h.observe(v)
        assert h.count_le(0.001) == 1
        assert h.count_le(0.01) == 2
        # off-bucket threshold snaps DOWN (errs toward alarming):
        # 0.05 -> largest bound <= it is 0.01
        assert h.count_le(0.05) == 2
        assert h.count_le(0.1) == 3
        assert h.count_le(99) == 3  # +Inf bucket never counts as good


# -- burn-rate window math ---------------------------------------------------


def _engine(tele, spec, **kw):
    return SLOEngine(tele, [parse_objective(spec)], clock=lambda: 0.0, **kw)


class TestBurnRates:
    def test_no_traffic_no_burn(self):
        tele = Telemetry(sample=1)
        eng = _engine(tele, "p99 delivery < 50ms over 10s/60s")
        eng.evaluate(0.0)
        eng.evaluate(10.0)
        st = next(iter(eng.state().values()))
        assert st["burn_rate_fast"] == 0 and not st["breached"]

    def test_breach_needs_both_windows_then_clears_on_fast(self):
        tele = Telemetry(sample=1)
        eng = _engine(tele, "p99 delivery < 50ms over 10s/40s")
        name = eng.objectives[0].name
        eng.evaluate(0.0)
        # 100% bad traffic: burn = 1.0/0.01 = 100x on any window with data
        for _ in range(50):
            tele.observe_delivery(1.0, "", 0, "local")
        eng.evaluate(5.0)
        st = eng.state()[name]
        assert st["breached"], st
        assert st["burn_rate_fast"] > 1 and st["burn_rate_slow"] > 1
        assert st["budget_remaining"] == 0.0
        # traffic turns good: the FAST window's delta goes clean while
        # the slow window still remembers the storm -> must clear
        for _ in range(5000):
            tele.observe_delivery(0.001, "", 0, "local")
        eng.evaluate(16.0)  # the bad burst has left the 10s fast window
        st = eng.state()[name]
        assert st["burn_rate_fast"] < 1.0
        assert not st["breached"]

    def test_one_bad_blip_does_not_breach(self):
        # bad events confined to a tiny fraction under the budget: the
        # burn stays below threshold on both windows
        tele = Telemetry(sample=1)
        eng = _engine(tele, "p99 delivery < 50ms over 10s/40s")
        eng.evaluate(0.0)
        tele.observe_delivery(1.0, "", 0, "local")  # 1 bad
        for _ in range(1000):  # 1000 good
            tele.observe_delivery(0.001, "", 0, "local")
        eng.evaluate(5.0)
        st = next(iter(eng.state().values()))
        assert not st["breached"]
        assert st["burn_rate_fast"] < 1.0

    def test_counter_reset_clamps_to_zero(self):
        tele = Telemetry(sample=1)
        eng = _engine(tele, "shed ratio < 1% over 10s/40s")
        tele.registry.counter("mqtt_tpu_messages_dropped_total").inc(100)
        tele.registry.counter("mqtt_tpu_messages_received_total").inc(200)
        eng.evaluate(0.0)
        # simulate a restart-style reset by a LOWER cumulative snapshot
        fam = tele.registry.counter("mqtt_tpu_messages_dropped_total")
        fam._value = 0
        eng.evaluate(5.0)
        st = next(iter(eng.state().values()))
        assert st["burn_rate_fast"] == 0.0

    def test_label_filtered_latency_objective(self):
        tele = Telemetry(sample=1)
        eng = _engine(tele, "p99 delivery{tenant=acme} < 50ms over 10s/40s")
        eng.evaluate(0.0)
        # the OTHER tenant melts down; acme stays healthy
        for _ in range(100):
            tele.observe_delivery(1.0, "bulk", 0, "local")
            tele.observe_delivery(0.001, "acme", 0, "local")
        eng.evaluate(5.0)
        st = next(iter(eng.state().values()))
        assert not st["breached"]
        assert st["burn_rate_fast"] == 0.0

    def test_gauges_exported_on_registry(self):
        tele = Telemetry(sample=1)
        eng = _engine(tele, "p99 delivery < 50ms over 10s/40s")
        eng.evaluate(0.0)
        text = tele.exposition()
        assert 'mqtt_tpu_slo_burn_rate{objective="' in text
        assert 'window="fast"' in text and 'window="slow"' in text
        assert "mqtt_tpu_slo_budget_remaining{" in text
        assert "mqtt_tpu_slo_breached{" in text
        assert "mqtt_tpu_slo_breaches_total" in text


# -- seeded latency-injection breach, end to end -----------------------------


class TestBreachEndToEnd:
    def test_breach_publishes_sys_sets_gauges_and_dumps(self, tmp_path):
        """The acceptance leg: a seeded latency injection drives a
        burn-rate breach — the retained $SYS/broker/slo/# transition
        reaches a live subscriber, the gauges flip, the flight dump is
        written — then recovery publishes the clearing transition."""

        async def scenario():
            h = Harness(
                Options(
                    inline_client=True,
                    slo_objectives=["p99 delivery < 50ms over 10s/40s"],
                    telemetry_dump_dir=str(tmp_path),
                )
            )
            srv = h.server
            assert srv.slo is not None
            name = srv.slo.objectives[0].name
            r, w, _ = await h.connect("slo-watcher", version=4)
            w.write(
                sub_packet(
                    1, [Subscription(filter="$SYS/broker/slo/#", qos=0)], 4
                )
            )
            await read_wire_packet(r, 4)

            rng = random.Random(7)
            srv.slo.evaluate(0.0)
            tele = srv.telemetry
            for _ in range(200):
                # seeded injection: every delivery lands 100-400ms past
                # the 50ms objective
                tele.observe_delivery(
                    0.1 + rng.random() * 0.3, "", 0, "local"
                )
            srv.slo.evaluate(5.0)

            pk = await read_wire_packet(r, 4)
            assert pk.fixed_header.type == PUBLISH
            assert pk.topic_name == "$SYS/broker/slo/" + name
            body = json.loads(bytes(pk.payload))
            assert body["breached"] is True
            assert body["burn_rate_fast"] > 1.0

            st = srv.slo.state()[name]
            assert st["breached"] and st["breaches"] == 1
            text = tele.exposition()
            assert (
                f'mqtt_tpu_slo_breached{{objective="{name}"}} 1' in text
            )
            # the one-bundle capture: the flight dump was written
            tele.recorder.join_writer()
            assert tele.recorder.dumps == 1
            dumps = list(tmp_path.glob("flight_*slo_breach*"))
            assert dumps, list(tmp_path.iterdir())

            # recovery: good traffic floods in, the fast window cools
            for _ in range(20000):
                tele.observe_delivery(0.001, "", 0, "local")
            srv.slo.evaluate(16.0)
            pk2 = await read_wire_packet(r, 4)
            assert json.loads(bytes(pk2.payload))["breached"] is False
            assert not srv.slo.state()[name]["breached"]
            await h.shutdown()

        run(scenario())

    def test_ratio_breach_from_real_broker_counters(self):
        """A shed-ratio objective burns off the broker's own Info
        mirrors (messages_dropped / messages_received)."""

        async def scenario():
            h = Harness(
                Options(
                    inline_client=True,
                    slo_objectives=["shed ratio < 1% over 10s/40s"],
                )
            )
            srv = h.server
            srv.slo.evaluate(0.0)
            srv.info.messages_received += 100
            srv.info.messages_dropped += 50  # 50% shed >> the 1% budget
            srv.slo.evaluate(5.0)
            st = next(iter(srv.slo.state().values()))
            assert st["breached"]
            assert st["burn_rate_fast"] > 1.0
            await h.shutdown()

        run(scenario())


# -- the delivery SLI through a real broker ----------------------------------


class TestDeliverySLI:
    def test_local_delivery_samples_with_tenant_and_qos_labels(self):
        async def scenario():
            h = Harness(Options(inline_client=True, telemetry_sample=1))
            srv = h.server
            sr, sw, _ = await h.connect("sli-sub", version=4)
            sw.write(sub_packet(1, [Subscription(filter="t/#", qos=1)], 4))
            await read_wire_packet(sr, 4)
            pr, pw, _ = await h.connect("sli-pub", version=4)
            pw.write(pub_packet("t/a", b"x", version=4, qos=1, pid=9))
            await read_wire_packet(pr, 4)  # PUBACK
            got = await read_wire_packet(sr, 4)
            assert got.fixed_header.type == PUBLISH
            text = srv.telemetry.exposition()
            assert (
                'mqtt_tpu_delivery_latency_seconds_count'
                '{path="local",qos="1",tenant=""}' in text
            )
            await h.shutdown()

        run(scenario())

    def test_slo_off_records_nothing(self):
        async def scenario():
            h = Harness(
                Options(inline_client=True, telemetry_sample=1, slo=False)
            )
            srv = h.server
            assert srv.telemetry.delivery_sli is False
            sr, sw, _ = await h.connect("sli-sub", version=4)
            sw.write(sub_packet(1, [Subscription(filter="t/#", qos=0)], 4))
            await read_wire_packet(sr, 4)
            pr, pw, _ = await h.connect("sli-pub", version=4)
            pw.write(pub_packet("t/a", b"x", version=4))
            await read_wire_packet(sr, 4)
            assert "mqtt_tpu_delivery_latency_seconds" not in (
                srv.telemetry.exposition()
            )
            await h.shutdown()

        run(scenario())

    def test_remote_clock_adds_origin_elapsed(self):
        tele = Telemetry(sample=1)
        clock = RemoteStageClock(0.25, "tid-1")
        clock.stamp("decode")
        tele.observe_delivery(
            clock.total() + clock.remote_base, "", 0, "remote",
            trace_id=clock.trace_id,
        )
        h = tele.delivery_hist("", 0, "remote")
        assert h.count == 1
        # the origin's 250ms elapsed stamp dominates the recorded value
        assert h.percentile(0.5) >= 0.25
        rows = tele.delivery_summary()
        assert rows["delivery_remote"]["count"] == 1

    def test_delivery_summary_folds_tenants_per_path(self):
        tele = Telemetry(sample=1)
        tele.observe_delivery(0.001, "", 0, "local")
        tele.observe_delivery(0.3, "acme", 1, "remote")
        rows = tele.delivery_summary()
        assert rows["delivery_local"]["count"] == 1
        assert rows["delivery_remote"]["p99_ms"] >= 300


# -- /healthz ----------------------------------------------------------------


class TestHealthz:
    def test_matrix_and_degraded_vs_not_ready(self):
        async def scenario():
            h = Harness(Options(inline_client=True))
            srv = h.server
            st = HTTPStats(
                LConfig(type="sysinfo", id="s", address="127.0.0.1:0"),
                srv.info,
                telemetry=srv.telemetry,
                health=srv.health_report,
            )
            await st.init(srv.log)
            host, port = st.address().rsplit(":", 1)
            data = await _http(host, port, "/healthz")
            head, body = data.split(b"\r\n\r\n", 1)
            assert head.startswith(b"HTTP/1.1 200")
            assert b"Cache-Control: no-store" in head
            report = json.loads(body)
            assert report["ok"] is True and report["not_ready"] == []

            # non-GET on the known path: 405 + Allow
            post = await _http(host, port, "/healthz", "POST")
            assert post.startswith(b"HTTP/1.1 405") and b"Allow: GET" in post

            # draining -> 503 with the failing component named
            srv._draining = True
            data = await _http(host, port, "/healthz")
            head, body = data.split(b"\r\n\r\n", 1)
            assert head.startswith(b"HTTP/1.1 503")
            assert "draining" in json.loads(body)["not_ready"]
            srv._draining = False

            # governor SHED -> 503 (the state property re-evaluates
            # lazily, so pin the internal state for the probe)
            from mqtt_tpu.overload import NORMAL, SHED

            srv.overload._state = SHED
            data = await _http(host, port, "/healthz")
            assert data.startswith(b"HTTP/1.1 503")
            assert b"governor_shed" in data
            srv.overload._state = NORMAL

            await st.close(lambda _: None)
            await h.shutdown()

        run(scenario())

    def test_404_without_health_fn(self):
        async def scenario():
            h = Harness()
            st = HTTPStats(
                LConfig(type="sysinfo", id="s", address="127.0.0.1:0"),
                h.server.info,
            )
            await st.init(h.server.log)
            host, port = st.address().rsplit(":", 1)
            assert (await _http(host, port, "/healthz")).startswith(
                b"HTTP/1.1 404"
            )
            # federation surfaces 404 without telemetry too
            assert (await _http(host, port, "/metrics/cluster")).startswith(
                b"HTTP/1.1 404"
            )
            assert (await _http(host, port, "/cluster/slo")).startswith(
                b"HTTP/1.1 404"
            )
            await st.close(lambda _: None)
            await h.shutdown()

        run(scenario())

    def test_staging_death_fails_readiness(self):
        """A dead staging pipeline must flip readiness (the component
        /healthz exists to catch)."""
        jax = pytest.importorskip("jax")  # noqa: F841

        async def scenario():
            h = Harness(
                Options(
                    inline_client=True,
                    device_matcher=True,
                    matcher_opts={"max_levels": 4, "background": False},
                )
            )
            srv = h.server
            await srv.serve()
            try:
                ok, detail = srv.health_report()
                assert ok and detail["staging"]["alive"]
                # kill the collector task: alive() must go false
                for t in srv._stage._tasks:
                    t.cancel()
                await asyncio.gather(
                    *srv._stage._tasks, return_exceptions=True
                )
                ok, detail = srv.health_report()
                assert not ok
                assert "staging_dead" in detail["not_ready"]
            finally:
                await srv.close()
                await h.shutdown()

        run(scenario())
