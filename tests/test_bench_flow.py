"""bench.py meets the device without a safety net (ISSUE 21).

The opposite of what this file asserted before: there is no probe
subprocess, no retry, no ``device_unreachable`` document and no exit
status 0 for a run whose device path failed. A config that needs the
device and cannot initialise its backend makes ``bench.py`` exit
non-zero and print no result; a run that works names its device in the
document and in every config's result object."""

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _bench(**env_over):
    env = dict(os.environ)
    env.update(
        BENCH_FAST="1",
        BENCH_HISTORY="0",
        PYTHONPATH=REPO + os.pathsep + env.get("PYTHONPATH", ""),
    )
    env.update(env_over)
    return subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py")],
        capture_output=True, timeout=420, env=env, cwd=REPO, text=True,
    )


def test_unusable_backend_fails_the_run():
    proc = _bench(
        JAX_PLATFORMS="nonexistent-backend",
        BENCH_CONFIGS="2,7",  # one device config + one host config
    )
    assert proc.returncode != 0
    assert "nonexistent-backend" in proc.stderr  # the error names the cause
    # nothing was caught into a partial document
    assert proc.stdout.strip() == ""


def test_every_result_names_its_device():
    proc = _bench(BENCH_CONFIGS="2,7", BENCH_SUBS="3000")
    assert proc.returncode == 0, proc.stderr[-800:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    want = {"platform": "cpu", "device_kind": "cpu"}
    for doc in (out, *out["configs"].values()):
        assert {k: doc[k] for k in want} == want
        assert doc["n_devices"] >= 1
    assert "device_unreachable" not in out and "probe_breaker" not in out
    assert out["value"] == out["configs"]["2_1m_plus"]["e2e_matches_per_sec"]
