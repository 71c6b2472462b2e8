"""BENCHMARK.json against the limits the driver refuses a manifest over,
before a single run: PR 22 was lost to one string in it."""

import json
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")
DATA_SUFFIXES = (".json", ".jsonl", ".toml", ".txt", ".csv")


@pytest.fixture(scope="module")
def manifest():
    path = os.path.join(ROOT, "BENCHMARK.json")
    assert os.path.getsize(path) <= 64 * 1024
    with open(path, encoding="utf-8") as f:
        return json.load(f)


def line(s, most=200):
    """1 to ``most`` printable ASCII characters, one line, no tab."""
    return (
        isinstance(s, str)
        and 1 <= len(s) <= most
        and all(" " <= c <= "~" for c in s)
    )


def test_top_level(manifest):
    assert set(manifest) == {
        "command", "paths", "run_seconds", "configs", "workloads",
        "end_to_end", "per_layer",
    }
    assert isinstance(manifest["run_seconds"], int)
    assert 1 <= manifest["run_seconds"] <= 51
    assert 1 <= len(manifest["paths"]) <= 16
    for p in manifest["paths"]:
        assert PATH.match(p) and not p.startswith("/") and ".." not in p
        assert os.path.isdir(os.path.join(ROOT, p))
    cmd = manifest["command"]
    assert 1 <= len(cmd) <= 32 and all(line(w) for w in cmd)
    for w in cmd:
        assert not w.startswith("/") and ".." not in w
        if os.path.exists(os.path.join(ROOT, w)):
            assert any(w.startswith(p + "/") for p in manifest["paths"])


def test_configs(manifest):
    configs = manifest["configs"]
    assert 1 <= len(configs) <= 24
    names = [c["name"] for c in configs]
    files = [c["file"] for c in configs]
    assert len(set(names)) == len(names) and len(set(files)) == len(files)
    used = {w["config"] for w in manifest["workloads"]}
    for c in configs:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["name"] in used
        assert line(c["source"]) and line(c["why"])
        assert PATH.match(c["file"])
        assert any(c["file"].startswith(p + "/") for p in manifest["paths"])
        assert len(c["reduced"]) <= 16
        with open(os.path.join(ROOT, c["file"]), encoding="utf-8") as f:
            body = json.load(f)
        assert body["source"] == c["source"]
        for key in c["reduced"]:
            assert NAME.match(key)
            assert key in body["reduced"], f"{c['name']}: {key} not explained"
        assert body["guarantees"], "a deployment states its guarantees"


def test_issue_sources_letter_for_letter(manifest):
    """ISSUE 24 gives both strings; the one whose cell was left out is
    held in its configuration file, for the PR that brings the cell."""
    by = {c["name"]: c["source"] for c in manifest["configs"]}
    with open(os.path.join(ROOT, "benchmark/configs/stresser-100.json"),
              encoding="utf-8") as f:
        by.setdefault("stresser-100", json.load(f)["source"])
    assert by["telemetry-1m"] == (
        "BASELINE.json configs[1] (north star of xyzj/mqtt-server graft): 1M "
        "subs, 3-level topics, 10% '+' single-level wildcards; data as "
        "bench.cfg2_subscriptions / cfg2_topic from --seed"
    )
    assert by["stresser-100"] == (
        "mochi-mqtt README 'Performance Benchmarks' (reference "
        "README.md:498-506, BASELINE.md): mqtt-stresser -num-clients=100 "
        "-num-messages=10000, the Million Message Challenge"
    )
    assert all(line(src) for src in by.values())


def test_workloads(manifest):
    cells = manifest["workloads"]
    assert 1 <= len(cells) <= 24
    names = [w["name"] for w in cells]
    assert len(set(names)) == len(names)
    pairs = [(w["config"], w["traffic"]) for w in cells]
    assert len(set(pairs)) == len(pairs)
    configs = {c["name"] for c in manifest["configs"]}
    for w in cells:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["name"] == f"{w['config']}.{w['traffic']}"
        assert w["config"] in configs
        assert w["chips"] == 1
        assert line(w["why"])
        mix = [
            f for f in os.listdir(os.path.join(ROOT, "benchmark", "traffic"))
            if os.path.splitext(f)[0] == w["traffic"]
        ]
        assert len(mix) == 1 and mix[0].endswith(DATA_SUFFIXES)


def reporting_cells(manifest, metric):
    return set(metric.get("workloads") or [w["name"] for w in manifest["workloads"]])


def test_metrics(manifest):
    e2e, per_layer = manifest["end_to_end"], manifest["per_layer"]
    assert 1 <= len(e2e) <= 16 and 1 <= len(per_layer) <= 128
    names = [m["name"] for m in e2e + per_layer]
    assert len(set(names)) == len(names)
    cells = {w["name"] for w in manifest["workloads"]}
    by_name = {m["name"]: m for m in e2e}
    assert by_name["setup_s"]["bound"] <= 0.25
    assert "workloads" not in by_name["setup_s"]
    for m in e2e:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in per_layer:
        assert set(m) - {"workloads"} == {
            "name", "unit", "better", "source", "layer", "moves",
        }
        assert m["source"] in SOURCES and line(m["layer"])
        moved = by_name[m["moves"]]
        assert reporting_cells(manifest, m) <= reporting_cells(manifest, moved), (
            f"{m['name']} is listed for a cell that does not report {m['moves']}"
        )
        if m["name"].endswith("_roofline") or "_roofline." in m["name"]:
            assert m["unit"] == "%"
        reader = m["name"].split(".")[0] + ".py"
        assert os.path.isfile(
            os.path.join(ROOT, "benchmark", "layer_metrics", reader)
        ), f"no reader {reader}"
    for m in e2e + per_layer:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert reporting_cells(manifest, m) <= cells
    for cell in cells:
        reports = [m["name"] for m in e2e if cell in reporting_cells(manifest, m)]
        assert "setup_s" in reports and len(reports) >= 2
        assert any(cell in reporting_cells(manifest, m) for m in per_layer)


def test_files_under_paths_are_named_from_name_characters(manifest):
    for p in manifest["paths"]:
        for base, dirs, files in os.walk(os.path.join(ROOT, p)):
            dirs[:] = [d for d in dirs if d not in ("__pycache__", ".work")]
            for f in files:
                rel = os.path.relpath(os.path.join(base, f), ROOT)
                assert PATH.match(rel), rel
