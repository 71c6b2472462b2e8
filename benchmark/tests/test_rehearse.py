"""A ``--rehearse`` run of each cell on the CPU: the last line keeps to
the contract. No number from these runs is a measurement."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf-8") as f:
    MANIFEST = json.load(f)
CELLS = [w["name"] for w in MANIFEST["workloads"]]
NEEDS_TRACE = {m["name"] for m in MANIFEST["per_layer"] if m["source"] == "device_trace"}


def names(kind, cell):
    return {m["name"] for m in MANIFEST[kind] if cell in m.get("workloads", [cell])}


def run(cell, *extra, seconds="3"):
    env = dict(os.environ, JAX_PLATFORMS="cpu", BENCH_RUN="ignored")
    proc = subprocess.run(
        [*MANIFEST["command"], "--workload", cell, "--seed", str(2**31 + 7),
         "--seconds", seconds, "--rehearse", *extra],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1]), proc.stderr


@pytest.mark.parametrize("cell", CELLS)
def test_untraced_line(cell):
    line, err = run(cell, "--trace", "0")
    assert list(line)[:5] == ["correct", "attempted", "failed", "metrics", "device"]
    assert list(line)[-1] == "compared"
    assert line["correct"] is True, err[-3000:]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert set(line["metrics"]) == names("end_to_end", cell)
    for m in line["metrics"].values():
        assert m["value"] > 0 and isinstance(m["unit"], str)
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(line["device"])
    for c in line["compared"].values():
        assert c["value"] <= c["limit"]
    tail = err.strip().splitlines()[-1 - len(line["compared"]):]
    assert tail[-1] == "correct: True"
    assert all(t.startswith("compared ") for t in tail[:-1])


@pytest.mark.parametrize("cell", CELLS)
def test_traced_line(cell):
    line, err = run(cell, "--trace", "1")
    assert line["correct"] is True, err[-3000:]
    # the CPU has no device plane: what reads the device trace finds
    # nothing and is left out, never reported as 0
    assert set(line["metrics"]) == names("per_layer", cell) - NEEDS_TRACE
    assert {"busy_s", "window_s"} <= set(line["device"])
    assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}


@pytest.mark.parametrize("cell", sorted({w["config"]: w["name"] for w in MANIFEST["workloads"]}.values()))
def test_the_control_comes_out_as_not_correct(cell):
    line, _err = run(cell, "--trace", "0", "--control")
    assert line["correct"] is False
    assert any(c["value"] > c["limit"] for c in line["compared"].values())


def test_a_cell_left_out_still_runs_as_an_experiment():
    """``stresser-100.challenge`` is not in BENCHMARK.json (PERF.md, Open
    questions: the program reorders one publisher's messages on the
    chip). Its files are here so that the fault can be shown again; at
    this size on the CPU the program keeps the order."""
    assert "stresser-100.challenge" not in CELLS
    line, err = run("stresser-100.challenge", "--trace", "0")
    assert line["correct"] is True, err[-3000:]
    assert line["metrics"]["delivered_per_s"]["value"] > 0
    line, _err = run("stresser-100.challenge", "--trace", "0", "--control")
    assert line["correct"] is False


def test_no_chip_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [*MANIFEST["command"], "--workload", CELLS[0], "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode != 0 and proc.stdout.strip() == ""


def test_generator_imports_neither_jax_nor_the_program():
    code = (
        "import sys; sys.argv=['generator.py']; "
        "import runpy; runpy.run_path('benchmark/generator.py', run_name='g'); "
        "bad=[m for m in sys.modules if m=='jax' or m.startswith(('jax.','mqtt_tpu'))]; "
        "print(bad); sys.exit(1 if bad else 0)"
    )
    proc = subprocess.run(
        [sys.executable, "-c", code], cwd=ROOT, capture_output=True, text=True
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
