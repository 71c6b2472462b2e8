"""chip_smoke.py's contract off the chip (ISSUE 21): the tiny-size CPU
mode runs the same script the chip is proven with, end to end; without
the flag a CPU-only box is refused by name and prints no result; alone
in a directory the script cannot pretend."""

import json
import os
import shutil
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def _run(args, cwd=REPO, script=SMOKE, pythonpath=True):
    env = dict(os.environ)
    if not pythonpath:
        env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, script, *args],
        capture_output=True, text=True, timeout=600, cwd=cwd, env=env,
    )


def test_tiny_cpu_mode_passes_and_reports():
    r = _run(["--expect-platform", "cpu", "--subs", "4000",
              "--publishes", "4000", "--seed", "5"])
    assert r.returncode == 0, r.stderr[-3000:]
    lines = r.stdout.strip().splitlines()
    assert len(lines) == 2  # the summary, then the verdict
    summary, out = lines[0], json.loads(lines[0])
    # the LAST line is the verdict, with exactly these keys
    verdict = json.loads(lines[1])
    assert verdict == {
        "ok": True,
        "device": {
            "platform": "cpu", "kind": out["device_kind"], "count": out["n_devices"],
        },
    }
    assert isinstance(verdict["device"]["kind"], str)
    assert type(verdict["device"]["count"]) is int
    assert out["failures"] == []
    assert (out["platform"], out["seed"], out["subs"], out["publishes"]) == (
        "cpu", 5, 4000, 4000,
    )
    assert set(out["versions"]) == {"jax", "jaxlib", "libtpu"}
    # the served leg: everything answered from device results, nothing
    # hidden behind a fallback
    assert out["delivery"]["expected"] > 0
    assert out["delivery"]["gaps"] == out["delivery"]["duplicates"] == 0
    assert out["served_topics"] == 4000 and out["device_share"] >= 0.9
    assert out["parity"]["sampled"] >= 1024 and out["parity"]["mismatches"] == 0
    assert out["pending_deltas"] == 0 and out["rebuild_errors"] == 0
    assert out["matcher"]["host_fast"] == 0
    for key in ("trips", "failures", "fallback_batches", "wedged_workers"):
        assert out["breaker"][key] == 0
    for klass in ("admission", "issue_error", "resolve_error"):
        assert out["staging"]["fallbacks"][klass] == 0
    assert out["compiles_second_half"] == 0
    # the cold batches were set-up, and counted as such
    assert out["staging"]["compile_tainted_batches"] >= 1
    # the roll-call: every kernel family ran and agreed with its oracle
    rc = out["roll_call"]
    for kernel in ("flat_match", "flat_match_ranges", "flat_match_packed",
                   "flat_match_compact", "scatter_rows"):
        assert rc["match_kernels"][kernel]["mismatches"] == 0
    assert rc["predicates"]["rules_eval"]["mismatches"] == 0
    assert rc["predicates"]["agg_reduce"]["mismatches"] == 0
    assert rc["keystream"]["mismatches"] == 0
    assert rc["retained_scan"]["mismatches"] == 0
    kernels = {c["kernel"] for c in out["compiles"]}
    assert {"flat_match", "flat_match_ranges", "flat_match_packed",
            "flat_match_compact", "scatter_rows", "rules_eval", "agg_reduce",
            "keystream"} <= kernels
    assert all(c["seconds"] >= 0 for c in out["compiles"])
    # set-up facts, not metrics
    for key in ("load_s", "build_s", "upload_s", "host_table_bytes",
                "cache_dir", "cache_entries_before", "cache_entries_after"):
        assert key in out
    assert out["cache_entries_after"] >= out["cache_entries_before"]
    assert out["native"]["lib"]["loaded"] and out["native"]["accel"]["loaded"]
    # no end-to-end number is claimed: the summary ENDS with it
    assert out["claim"] is None and summary.endswith('"claim": null}')


def test_cpu_only_box_is_refused_by_name():
    r = _run(["--subs", "4000", "--publishes", "4000"])
    assert r.returncode != 0
    assert "'cpu'" in r.stderr and "expected 'tpu'" in r.stderr
    assert r.stdout.strip() == ""  # no result


def test_alone_in_a_directory_it_fails(tmp_path):
    alone = tmp_path / "chip_smoke.py"
    shutil.copy(SMOKE, alone)
    r = _run(["--expect-platform", "cpu"], cwd=str(tmp_path),
             script=str(alone), pythonpath=False)
    assert r.returncode != 0
    assert r.stdout.strip() == ""
