"""The per-device observability plane (ISSUE 18, mqtt_tpu.ops.
devicestats): skew math, the compile-event ledger's determinism and
attribution, labeled-family exposition on the 8-way CPU-jax mesh, the
profiler's per-device windows (parity vs the single-device aggregate
oracle), the steady-state recompile regression guard (the PR 11
incident), the /devices HTTP matrix, the devices_*.json dump sibling,
the shard-skew SLO objective end-to-end, and the /healthz degraded
entries. The suite-wide conftest forces 8 XLA host devices, so every
test here sees the MULTICHIP topology.
"""

import json
import time

import numpy as np
import pytest

from mqtt_tpu import Options
from mqtt_tpu.listeners import Config as LConfig, HTTPStats
from mqtt_tpu.ops.devicestats import (
    LEDGER,
    CompileLedger,
    DeviceStatsPlane,
    KernelWatch,
    skew_of,
)
from mqtt_tpu.packets import Subscription
from mqtt_tpu.telemetry import Telemetry, check_exposition
from mqtt_tpu.topics import SYS_PREFIX, TopicsIndex
from mqtt_tpu.tracing import BatchProfile, DeviceProfiler

from tests.test_server import Harness, run
from tests.test_telemetry import _http

jax = pytest.importorskip("jax")


def _mesh_matcher(n_subs: int = 40):
    from mqtt_tpu.parallel.sharded import ShardedTpuMatcher, make_mesh

    index = TopicsIndex()
    for i in range(n_subs):
        index.subscribe(f"c{i}", Subscription(filter=f"a/{i % 8}/b"))
        index.subscribe(f"w{i}", Subscription(filter=f"a/{i % 8}/+"))
    return ShardedTpuMatcher(index, mesh=make_mesh(jax.devices()[:8]))


# -- skew math ---------------------------------------------------------------


class TestSkewMath:
    def test_balanced_is_one(self):
        assert skew_of([100, 100, 100, 100]) == pytest.approx(1.0)

    def test_one_hot_tile_is_tile_count(self):
        assert skew_of([400, 0, 0, 0]) == pytest.approx(4.0)

    def test_crafted_distribution(self):
        assert skew_of([30, 10]) == pytest.approx(1.5)

    def test_no_traffic_and_empty_claim_nothing(self):
        assert skew_of([]) == 0.0
        assert skew_of([0, 0, 0]) == 0.0

    def test_numpy_input(self):
        assert skew_of(np.array([8, 4, 4], dtype=np.int64)) == pytest.approx(
            1.5
        )


# -- compile ledger ----------------------------------------------------------


class TestCompileLedger:
    def test_watch_notes_first_call_per_signature_only(self):
        led = CompileLedger()
        calls = []
        w = KernelWatch("k", lambda *a, **kw: calls.append(1), ledger=led)
        x = np.zeros((16, 4), np.int32)
        for _ in range(5):
            w(x, capacity=128)
        assert led.total() == 1 and led.count("k") == 1
        # a new shape OR a new static is a new compile event
        w(np.zeros((32, 4), np.int32), capacity=128)
        w(x, capacity=256)
        assert led.total() == 3
        assert len(calls) == 7  # the wrapped fn ran every time

    def test_compile_clock_covers_running_and_finished_first_calls(self):
        """The compile clock (staging + watchdog read it) runs while a
        first-signature call is IN FLIGHT, keeps its seconds once the
        call ends — also when it raises — and stands still on a warm
        signature."""
        import threading

        led = CompileLedger()
        entered, release = threading.Event(), threading.Event()

        def kernel(x, fail=False):
            entered.set()
            release.wait(5)
            if fail:
                raise RuntimeError("compiler refused")

        w = KernelWatch("k", kernel, ledger=led)
        x = np.zeros((16, 4), np.int32)
        assert led.compile_clock() == 0.0
        t = threading.Thread(target=w, args=(x,))
        t.start()
        assert entered.wait(5)
        c1 = led.compile_clock()
        time.sleep(0.05)
        c2 = led.compile_clock()
        assert c2 > c1 >= 0.0  # in flight: the clock is running
        release.set()
        t.join(5)
        assert not t.is_alive()
        done = led.compile_clock()
        assert done >= c2 and led.compile_clock() == done  # stopped
        w(x)  # warm signature: no clock movement, no ledger event
        assert led.compile_clock() == done and led.total() == 1
        with pytest.raises(RuntimeError):
            w(np.zeros((32, 4), np.int32), fail=True)
        assert led.compile_clock() >= done and led.total() == 1
        assert not led._compiling  # every window closed

    def test_attribution_names_kernel_and_shapes(self):
        led = CompileLedger()
        w = KernelWatch("flat_match_compact", lambda *a, **kw: None, ledger=led)
        since = led.total()
        w(np.zeros((64, 8), np.int32), capacity=512)
        text = led.attribution(since)
        assert "1 compile event(s)" in text
        assert "flat_match_compact[64x8,capacity=512]" in text
        assert led.attribution(led.total()) == "no compile events recorded"

    def test_registry_binding_exports_counter_and_histogram(self):
        led = CompileLedger()
        tele = Telemetry()
        led.bind_registry(tele.registry)
        w = KernelWatch("rules_eval", lambda *a, **kw: time.sleep(0.001), ledger=led)
        w(np.zeros((4,), np.int32))
        text = tele.exposition()
        assert 'mqtt_tpu_matcher_recompiles_total{kernel="rules_eval"} 1' in text
        assert "mqtt_tpu_matcher_compile_seconds_count 1" in text
        assert check_exposition(text) > 0

    def test_snapshot_shape(self):
        led = CompileLedger()
        led.note_compile("k1", "8x4", 0.25)
        led.note_compile("k2", "16x4", 0.5)
        snap = led.snapshot()
        assert snap["total"] == 2 and snap["kernels"] == {"k1": 1, "k2": 1}
        assert snap["recent"][-1]["kernel"] == "k2"
        assert snap["seconds"]["count"] == 2


# -- the PR 11 regression guard: steady-state recompiles == 0 ----------------


class TestRecompileGuard:
    def test_steady_state_recompiles_stay_flat(self):
        """Pinned capacity + batch sizes inside one pow2 bucket: after
        warmup the device matcher must never recompile — the exact
        silent-3x failure mode PR 11 hit. A failure prints the ledger's
        kernel/shape attribution so the regression is named, not just
        counted."""
        from mqtt_tpu.ops import TpuMatcher

        index = TopicsIndex()
        for i in range(60):
            index.subscribe(f"c{i}", Subscription(filter=f"s/{i % 12}/+"))
        m = TpuMatcher(index, max_levels=4, compact=True, compact_capacity=256)
        m.rebuild()
        topics = [f"s/{i % 12}/x" for i in range(200)]
        m.match_topics(topics)  # warmup: compiles the 256-topic bucket
        since = LEDGER.total()
        for b in (201, 223, 256, 199):  # all pad to the same 256 bucket
            m.match_topics([f"s/{i % 12}/y" for i in range(b)])
        delta = LEDGER.total() - since
        assert delta == 0, (
            f"steady-state recompiles must stay flat; got {delta}:\n"
            + LEDGER.attribution(since)
        )

    def test_capacity_churn_is_caught_with_attribution(self):
        """Deliberately defeat the capacity hysteresis (fresh capacity
        per dispatch, the pre-PR-11 behavior): the ledger must record
        the recompiles and attribute them to the compact kernel."""
        from mqtt_tpu.ops import TpuMatcher

        index = TopicsIndex()
        for i in range(60):
            index.subscribe(f"c{i}", Subscription(filter=f"s/{i % 12}/+"))
        # pinned capacity forces the compact path (_compact_pays) so the
        # churn below exercises the exact kernel PR 11 thrashed
        m = TpuMatcher(index, max_levels=4, compact=True, compact_capacity=64)
        m.rebuild()
        topics = [f"s/{i % 12}/x" for i in range(100)]
        m.match_topics(topics)  # warm the pinned capacity's executable
        # churn: odd capacities no other test compiles, one per dispatch
        caps = iter((24, 56, 24, 56))
        m._compact_capacity_for = lambda b, flat: next(caps)
        since = LEDGER.total()
        m.match_topics(topics)
        m.match_topics(topics)
        delta = LEDGER.total() - since
        assert delta >= 2, LEDGER.attribution(since)
        assert "flat_match_compact" in LEDGER.attribution(since)


# -- per-device profiler windows ---------------------------------------------


class TestPerDeviceWindows:
    @staticmethod
    def _feed(prof, devices, n=4, d2h_bytes=4096):
        t = time.perf_counter()
        for i in range(n):
            rec = BatchProfile()
            rec.devices = devices
            rec.d2h_bytes = d2h_bytes
            base = t + i * 1e-3
            prof.note_dispatch(rec, base, base + 2e-4)
            prof.note_resolve(rec, base + 3e-4, base + 4e-4)

    def test_single_device_window_matches_aggregate_oracle(self):
        """Window 0 of an unstamped (devices=None) run must be
        bit-identical to the pre-ISSUE-18 aggregate fold — the parity
        oracle that proves the per-device replica arithmetic."""
        prof = DeviceProfiler()
        self._feed(prof, None)
        dev = prof.device_snapshot()
        assert list(dev.keys()) == [0]
        d0 = dev[0]

        def p99_ms(hist):
            return round(hist.percentile(0.99) * 1e3, 3)

        assert d0["batches"] == prof.batches == 4
        assert d0["duty_cycle"] == round(prof.duty_cycle(), 4)
        assert d0["overlap_ratio"] == round(prof.overlap_ratio(), 4)
        assert d0["issue_p99_ms"] == p99_ms(prof.issue_hist)
        assert d0["d2h_p99_ms"] == p99_ms(prof.d2h_hist)
        assert d0["idle_gap_p99_ms"] == p99_ms(prof.idle_gap_hist)

    def test_multi_device_stamp_splits_bytes_evenly(self):
        prof = DeviceProfiler()
        self._feed(prof, (0, 1, 2, 3), n=2, d2h_bytes=8192)
        dev = prof.device_snapshot()
        assert sorted(dev.keys()) == [0, 1, 2, 3]
        for d in dev.values():
            assert d["batches"] == 2
            assert d["d2h_bytes_total"] == 2 * 8192 // 4

    def test_labeled_children_registered_once_per_device(self):
        tele = Telemetry()
        prof = DeviceProfiler(registry=tele.registry)
        self._feed(prof, (0, 1))
        self._feed(prof, (0, 1))
        text = tele.exposition()
        for did in ("0", "1"):
            assert f'mqtt_tpu_device_duty_cycle_ratio{{device="{did}"}}' in text
            assert f'device="{did}"' in text
        assert check_exposition(text) > 0


# -- the 8-way mesh: labeled families + skew + tiles end-to-end --------------


class TestMeshExposition:
    def test_all_eight_devices_and_tiles_exported(self):
        tele = Telemetry()
        plane = DeviceStatsPlane(registry=tele.registry)
        prof = DeviceProfiler(registry=tele.registry)
        plane.attach_profiler(prof)
        m = _mesh_matcher()
        m.profiler = prof
        plane.attach_matcher(m)
        for _ in range(3):
            m.match_topics([f"a/{i % 8}/b" for i in range(32)])
        text = tele.exposition()
        for did in range(8):
            assert f'mqtt_tpu_device_hbm_ratio{{device="{did}"}}' in text
            assert (
                f'mqtt_tpu_device_duty_cycle_ratio{{device="{did}"}}' in text
            )
        assert "mqtt_tpu_device_skew_ratio" in text
        assert "mqtt_tpu_device_d2h_bytes_bucket" in text
        for t in range(m.n_batch):
            assert f'mqtt_tpu_device_tile_hits_total{{tile="{t}"}}' in text
            assert f'tile="{t}"' in text
        assert "mqtt_tpu_matcher_recompiles_total" in text
        assert check_exposition(text) > 0

        snap = plane.snapshot()
        assert snap["n_devices"] == 8
        assert len(snap["devices"]) == 8
        assert all(d["batches"] >= 1 for d in snap["devices"])
        assert snap["skew"]["ratio"] > 0.0
        assert snap["compiles"]["total"] >= 1
        # an even workload across 8 sub-families lands near balanced
        assert plane.skew_ratio() == pytest.approx(1.0, abs=0.5)

    def test_crafted_imbalance_moves_the_gauge(self):
        m = _mesh_matcher()
        hits = np.full(m.n_batch, 10, dtype=np.int64)
        hits[0] = 300  # one hot tile
        m._fold_tile_hits(hits, cap_local=512)
        expected = skew_of(hits)
        assert m.device_skew_ratio() == pytest.approx(expected)
        # max/mean on an n-tile mesh tops out just below n; a 30x hot
        # tile must land well clear of balanced (1.0)
        assert expected > 1.5
        assert m.tile_hit_counts().tolist() == hits.tolist()
        # per-tile fill histograms saw one batch each at hits/cap
        assert m.tile_fill_hists[0].count == 1
        assert m.tile_fill_hists[0].percentile(0.5) >= 300 / 512

    def test_hbm_snapshot_graceful_on_cpu_backend(self):
        plane = DeviceStatsPlane()
        snap = plane.snapshot()
        for d in snap["devices"]:
            # CPU-jax either answers memory_stats or the plane degrades
            # to None/-1 sentinels — never a crash, never a fake number
            hbm = d["hbm"]
            assert set(hbm) == {"live_bytes", "peak_bytes", "limit_bytes", "ratio"}
        assert snap["hbm"]["degraded"] in (False,)
        tree = plane.sys_tree()
        assert "skew_ratio" in tree
        assert "0/hbm_live_bytes" in tree and "compiles/total" in tree


# -- /devices HTTP matrix ----------------------------------------------------


class TestDevicesEndpoint:
    def test_matrix(self):
        async def scenario():
            tele = Telemetry()
            plane = DeviceStatsPlane(registry=tele.registry)
            tele.attach_device_stats(plane)
            st = HTTPStats(
                LConfig(type="sysinfo", id="d", address="127.0.0.1:0"),
                None,
                telemetry=tele,
            )
            await st.init(None)
            host, port = st.address().rsplit(":", 1)
            data = await _http(host, port, "/devices")
            head, body = data.split(b"\r\n\r\n", 1)
            assert head.startswith(b"HTTP/1.1 200")
            assert b"Cache-Control: no-store" in head
            assert b"application/json" in head
            doc = json.loads(body)
            assert doc["n_devices"] == 8
            assert {d["id"] for d in doc["devices"]} == set(range(8))
            post = await _http(host, port, "/devices", "POST")
            assert post.startswith(b"HTTP/1.1 405") and b"Allow: GET" in post
            await st.close(lambda _: None)

        run(scenario())

    def test_404_without_plane(self):
        async def scenario():
            st = HTTPStats(
                LConfig(type="sysinfo", id="d", address="127.0.0.1:0"),
                None,
                telemetry=Telemetry(),
            )
            await st.init(None)
            host, port = st.address().rsplit(":", 1)
            assert (await _http(host, port, "/devices")).startswith(
                b"HTTP/1.1 404"
            )
            await st.close(lambda _: None)

        run(scenario())


# -- dump bundle + skew SLO end-to-end ---------------------------------------


class TestDumpAndSkewSLO:
    def test_trigger_dump_writes_devices_sibling(self, tmp_path):
        tele = Telemetry(dump_dir=str(tmp_path), dump_min_interval_s=0.0)
        plane = DeviceStatsPlane(registry=tele.registry)
        tele.attach_device_stats(plane)
        tele.trigger_dump("unit_test")
        tele.recorder.join_writer()
        flights = sorted(tmp_path.glob("flight_*.json"))
        devices = sorted(tmp_path.glob("devices_*.json"))
        assert len(flights) == 1 and len(devices) == 1
        # sibling naming: devices_<flight stem sans prefix>.json
        assert devices[0].name == "devices_" + flights[0].name[len("flight_"):]
        doc = json.load(open(devices[0]))
        assert doc["n_devices"] == 8 and "compiles" in doc

    def test_skew_objective_breach_fires_bundle(self, tmp_path):
        """The acceptance leg: a 'shard skew < 2.0' objective burning
        against the live gauge breaches, /healthz degrades with
        device_skew, and the dump bundle grows the devices sibling."""

        async def scenario():
            h = Harness(
                Options(
                    inline_client=True,
                    slo_objectives=["shard skew < 2.0 over 10s/40s"],
                    telemetry_dump_dir=str(tmp_path),
                )
            )
            srv = h.server
            plane = srv.device_stats
            assert plane is not None and srv.slo is not None
            obj = srv.slo.objectives[0]
            assert obj.kind == "gauge"
            assert obj.family == "mqtt_tpu_device_skew_ratio"

            class HotTile:
                @staticmethod
                def device_skew_ratio() -> float:
                    return 5.0  # one tile doing 5x its share

            plane.matcher = HotTile()
            srv.slo.evaluate(0.0)
            for i in range(1, 4):
                srv.slo.evaluate(float(5 * i))
            st = srv.slo.state()[obj.name]
            assert st["breached"] and st["value"] == pytest.approx(5.0)
            assert st["threshold"] == pytest.approx(2.0)

            ok, report = srv.health_report()
            assert ok is True  # degraded NEVER flips readiness
            assert "device_skew" in report["degraded"]
            assert report["devices"]["skew_ratio"] == pytest.approx(5.0)

            srv.telemetry.recorder.join_writer()
            assert sorted(tmp_path.glob("flight_*slo_breach*"))
            assert sorted(tmp_path.glob("devices_*slo_breach*"))

            # balance restored: the gauge drops, the breach clears
            plane.matcher = None
            for i in range(4, 40):
                srv.slo.evaluate(float(5 * i))
            assert not srv.slo.state()[obj.name]["breached"]
            assert "device_skew" not in srv.health_report()[1]["degraded"]
            await h.shutdown()

        run(scenario())


# -- /healthz device plane + $SYS tree ---------------------------------------


class TestHealthzDevices:
    def test_hbm_watermark_degrades_but_stays_ready(self):
        async def scenario():
            h = Harness(Options(inline_client=True, device_hbm_watermark=0.8))
            srv = h.server
            plane = srv.device_stats
            assert plane is not None
            ok, report = srv.health_report()
            assert ok is True
            assert "devices" in report and report["degraded"] == []

            plane.hbm_ratio = lambda: 0.93  # above the 0.8 watermark
            ok, report = srv.health_report()
            assert ok is True and report["not_ready"] == []
            assert "hbm_watermark" in report["degraded"]
            assert report["devices"]["hbm_ratio"] == pytest.approx(0.93)

            plane.hbm_ratio = lambda: 0.0  # backend can't answer: healthy
            assert "hbm_watermark" not in srv.health_report()[1]["degraded"]
            await h.shutdown()

        run(scenario())

    def test_host_only_broker_never_enumerates_the_backend(self):
        """A chip belongs to one process: a broker with no device engine
        keeps the ledger-only plane (compile counts, no per-device
        rows), so a host-only worker cannot take the chip."""

        async def scenario():
            h = Harness(Options(inline_client=True))
            snap = h.server.device_stats.snapshot()
            assert snap["n_devices"] == 0 and snap["devices"] == []
            assert "compiles" in snap
            await h.shutdown()

        run(scenario())

    def test_device_stats_off_removes_plane_and_endpoint(self):
        async def scenario():
            h = Harness(Options(inline_client=True, device_stats=False))
            srv = h.server
            assert srv.device_stats is None
            assert "devices" not in srv.health_report()[1]
            await h.shutdown()

        run(scenario())

    def test_sys_tree_rows_published(self):
        async def scenario():
            # per-device rows need a device engine: a host-only broker
            # never enumerates (= initializes) the backend
            h = Harness(Options(inline_client=True, device_matcher=True))
            srv = h.server
            srv.publish_sys_topics()
            pks = srv.topics.messages(SYS_PREFIX + "/broker/devices/#")
            tree = {p.topic_name: bytes(p.payload) for p in pks}
            assert tree, "devices $SYS tree must publish retained rows"
            assert SYS_PREFIX + "/broker/devices/skew_ratio" in tree
            assert SYS_PREFIX + "/broker/devices/compiles/total" in tree
            assert SYS_PREFIX + "/broker/devices/0/hbm_live_bytes" in tree
            await h.shutdown()

        run(scenario())
