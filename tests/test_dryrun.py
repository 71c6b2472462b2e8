"""Driver-contract tests for __graft_entry__.dryrun_multichip.

The dryrun must never initialize an accelerator backend (a chip belongs
to one process). These tests run it in a fresh subprocess with the
platform deliberately poisoned: if any code path queries a non-CPU
backend, the run dies; passing proves the dryrun is hermetic to virtual
CPU devices.
"""

import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CODE = (
    "import __graft_entry__ as g; g.dryrun_multichip(8); print('DRYRUN_OK')"
)


def _run(env_overrides: dict, drop: tuple = ()) -> subprocess.CompletedProcess:
    env = {k: v for k, v in os.environ.items() if k not in drop}
    env.update(env_overrides)
    return subprocess.run(
        [sys.executable, "-c", CODE],
        cwd=REPO,
        env=env,
        capture_output=True,
        text=True,
        timeout=600,
    )


def test_dryrun_clean_env():
    """No JAX_PLATFORMS / XLA_FLAGS at all: the dryrun must provision its
    own 8 virtual CPU devices."""
    r = _run({}, drop=("JAX_PLATFORMS", "XLA_FLAGS"))
    assert r.returncode == 0, r.stderr[-3000:]
    assert "DRYRUN_OK" in r.stdout


def test_dryrun_poisoned_tpu_platform():
    """JAX_PLATFORMS=tpu poison: if the dryrun did not pin the platform to
    cpu before backend init, jax would try (and in a chip-less sandbox
    fail) to bring up the accelerator backend. Passing proves the
    override."""
    r = _run({"JAX_PLATFORMS": "tpu"}, drop=("XLA_FLAGS",))
    assert r.returncode == 0, r.stderr[-3000:]
    assert "DRYRUN_OK" in r.stdout


def test_dryrun_small_xla_flags_raised():
    """A pre-set XLA_FLAGS with too few host devices must be raised, not
    trusted."""
    r = _run(
        {"XLA_FLAGS": "--xla_force_host_platform_device_count=2"},
        drop=("JAX_PLATFORMS",),
    )
    assert r.returncode == 0, r.stderr[-3000:]
    assert "DRYRUN_OK" in r.stdout
