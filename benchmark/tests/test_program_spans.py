"""The per-layer metrics that read the program's own record of the traced
slice (``program_spans.py`` over ``mqtt_tpu.tracing.last_slice()``), on
``--rehearse --trace 1`` runs of each cell in this process, and the tool
that lays the program's host spans over the device's idle gaps. CPU runs:
counts and consistency, never a measurement."""

import json
import math
import os
import sys

import pytest

import run as harness

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "tools"))

import host_gaps  # noqa: E402
import program_spans  # noqa: E402

READERS = ("publish_wait_ms", "batch_busy_us_per_pub", "cpu_us_per_pub",
           "loop_us_per_pub", "batch_inflight_share", "loop_stall_max_ms")
with open(os.path.join(harness.ROOT, "BENCHMARK.json"), encoding="utf-8") as f:
    MANIFEST = json.load(f)
CELLS = [w["name"] for w in MANIFEST["workloads"]]


def new_metrics(cell):
    return [
        m for m in MANIFEST["per_layer"]
        if m["name"].split(".")[0] in READERS and cell in m["workloads"]
    ]


def test_the_manifest_lists_sixteen_a_cell():
    for cell in CELLS:
        assert len(new_metrics(cell)) == 16
    for m in MANIFEST["per_layer"]:
        if m["name"].split(".")[0] in READERS:
            steady = m["name"].endswith(".steady")
            assert m["moves"] == ("delay_p50_ms" if steady else "publish_per_s")
            assert m["source"] in ("program_span", "program_counter")


@pytest.mark.parametrize("cell", CELLS)
def test_traced_line_carries_the_program_spans(cell, capsys, monkeypatch):
    seen = {}
    inner = harness.per_layer_metrics

    def spy(spec, ctx):
        seen["ctx"] = ctx
        return inner(spec, ctx)

    monkeypatch.setattr(harness, "per_layer_metrics", spy)
    monkeypatch.setattr(program_spans, "_noted", False)
    rc = harness.main(
        ["--workload", cell, "--seed", str(2**31 + 11), "--seconds", "6",
         "--trace", "1", "--rehearse"]
    )
    assert rc == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["correct"] is True
    values = {}
    for m in new_metrics(cell):
        assert m["name"] in line["metrics"], m["name"]
        got = line["metrics"][m["name"]]
        assert got["unit"] == m["unit"]
        assert math.isfinite(got["value"]) and got["value"] > 0, m["name"]
        values[m["name"].removesuffix(".steady")] = got["value"]
    # the three thread groups are the process: their sum is the harness's
    # own process CPU per topic over the same slice (us a publish), and
    # the slice saw the topics the harness counted
    counters = seen["ctx"]["trace"]["counters"]
    outside = 1e6 * counters["cpu_s"] / counters["topics"]
    inside = sum(values[f"cpu_us_per_pub.{g}"] for g in ("loop", "match", "other"))
    assert inside == pytest.approx(outside, rel=0.15)
    sl = program_spans.load()
    assert program_spans.delta(sl, "topics") == pytest.approx(counters["topics"], rel=0.15)
    assert 0 < values["batch_inflight_share"] <= 100
    # a publish's loop time is inside its wait: busy is part of the stay
    assert values["loop_us_per_pub.fanout"] / 1e3 <= values["publish_wait_ms.fanout"]


def test_a_program_without_the_record_reads_nothing(monkeypatch):
    """A parent commit has no ``last_slice``; ``--trace 0`` leaves none:
    every reader then returns None, never 0, and does not raise."""
    import importlib

    from mqtt_tpu import tracing

    for missing in (False, True):
        if missing:
            monkeypatch.delattr(tracing, "last_slice")
        else:
            monkeypatch.setattr(tracing, "_LAST_SLICE", None)
        for cell in CELLS:
            for m in new_metrics(cell):
                reader = importlib.import_module(
                    "layer_metrics." + m["name"].split(".")[0]
                )
                assert reader.read({"metric": m["name"], "trace": None}) is None


class Ev:
    def __init__(self, name, start, dur, **stats):
        self.name, self.start_ns, self.duration_ns = name, start, dur
        self.stats = list(stats.items())


class Line:
    def __init__(self, name, events):
        self.name, self.events = name, events


class Plane:
    def __init__(self, name, lines):
        self.name, self.lines = name, lines


class Profile:
    def __init__(self, planes):
        self.planes = planes


def test_host_gaps_lays_the_host_spans_over_the_idle_gaps():
    device = Plane("/device:TPU:0", [
        Line("XLA Ops", [Ev("%a", 0, 10), Ev("%b", 5, 10),  # busy 0..15
                         Ev("%c", 115, 5),                  # gap 15..115
                         Ev("%d", 150, 5)]),                # gap 120..150
        Line("XLA Modules", [Ev("jit__packed_core", 0, 15)]),
    ])
    host = Plane("/host:CPU", [
        Line("mqtt-tpu-guard-1", [
            Ev("mqtt/tokenize", 20, 30, batch=7),       # 30 inside gap 1
            Ev("mqtt/h2d_dispatch", 50, 80, batch=7),   # 65 of it inside gap 1
            Ev("something/else", 20, 90),
        ]),
        Line("MainThread", [
            Ev("mqtt/deliver.futures", 10, 10, batch=6),  # 5 inside gap 1
            Ev("mqtt/deliver.futures", 140, 30, batch=7),  # 10 inside gap 2
        ]),
    ])
    out = host_gaps.attribute(Profile([device, host]), top=10)
    assert out["device_planes"] == 1 and out["host_spans"] == 4
    first, second = out["gaps"]
    assert first["gap_ms"] == pytest.approx(100 / 1e6) and first["ended_by"] == "%c"
    assert first["host"]["mqtt/h2d_dispatch"] == {"ms": 0.0, "n": 1, "batches": [7]}
    assert set(first["host"]) == {
        "mqtt/tokenize", "mqtt/h2d_dispatch", "mqtt/deliver.futures",
    }
    # 15..20 by deliver, 20..50 tokenize, 50..115 dispatch: all covered
    assert first["uncovered_ms"] == pytest.approx(0.0)
    assert second["gap_ms"] == pytest.approx(30 / 1e6)
    assert list(second["host"]) == ["mqtt/h2d_dispatch", "mqtt/deliver.futures"]
    assert second["uncovered_ms"] == pytest.approx(10 / 1e6)  # 130..140
    assert host_gaps.covered([(0, 10), (5, 20), (40, 50)], 8, 45) == 12 + 5


def test_host_gaps_on_a_trace_recorded_before_the_spans_existed(capsys):
    """PR 24's recorded slice: ten gaps, and no ``mqtt/*`` span to lay
    over them (the whole of each gap reads as uncovered)."""
    trace = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                         "saturate_slice.xplane.pb")
    assert host_gaps.main([trace, "--json"]) == 0
    out = json.loads(capsys.readouterr().out)
    assert out["device_planes"] == 1 and out["host_spans"] == 0
    assert len(out["gaps"]) == 10
    for g in out["gaps"]:
        assert g["host"] == {} and g["uncovered_ms"] == pytest.approx(g["gap_ms"])
    assert out["gaps"][0]["gap_ms"] > 400
    assert host_gaps.main([trace, "--top", "3"]) == 0
    text = capsys.readouterr().out
    assert text.count("gap ") == 3 and "(no mqtt/* span)" in text
