"""One process per chip (ISSUE 21): a launcher refuses, at launch and by
name, a worker fleet whose every process would initialize the default
JAX device; a broker with no device engine never imports a backend."""

import os
import subprocess
import sys

import pytest

from mqtt_tpu.cluster import ChipConflictError, require_one_process_per_chip

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _env(**over):
    env = {k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"}
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env.update(over)
    return env


class TestLaunchRule:
    def test_single_worker_or_host_mesh_is_always_fine(self, monkeypatch):
        monkeypatch.setenv("JAX_PLATFORMS", "tpu,cpu")
        require_one_process_per_chip(1, True)
        require_one_process_per_chip(8, False)

    @pytest.mark.parametrize("platforms", ["cpu", "CPU", "cpu,tpu"])
    def test_host_platform_is_shareable(self, monkeypatch, platforms):
        monkeypatch.setenv("JAX_PLATFORMS", platforms)
        require_one_process_per_chip(4, True)

    @pytest.mark.parametrize("platforms", [None, "", "tpu", "tpu,cpu"])
    def test_possible_accelerator_is_refused_by_name(
        self, monkeypatch, platforms
    ):
        if platforms is None:
            monkeypatch.delenv("JAX_PLATFORMS", raising=False)
        else:
            monkeypatch.setenv("JAX_PLATFORMS", platforms)
        with pytest.raises(ChipConflictError) as e:
            require_one_process_per_chip(2, True)
        msg = str(e.value)
        assert "2 workers" in msg and "one process" in msg
        assert "JAX_PLATFORMS" in msg


class TestLaunchers:
    def test_stress_launcher_fails_at_launch(self):
        """The launcher must die before it spawns anything: no worker may
        reach backend init (on the chip that is where the loser of the
        race used to die, or hang)."""
        r = subprocess.run(
            [sys.executable, "-m", "mqtt_tpu.stress", "--serve",
             "--broker", "127.0.0.1:0", "--workers", "2", "--device-matcher"],
            env=_env(JAX_PLATFORMS="tpu,cpu"), cwd=REPO, text=True,
            capture_output=True, stdin=subprocess.DEVNULL, timeout=60,
        )
        assert r.returncode != 0
        assert "ChipConflictError" in r.stderr
        assert "chip belongs to one process" in r.stderr
        assert "READY" not in r.stdout

    def test_cli_launcher_fails_at_launch(self, tmp_path):
        cfg = tmp_path / "broker.yaml"
        cfg.write_text("options:\n  device_matcher: true\n")
        r = subprocess.run(
            [sys.executable, "-m", "mqtt_tpu", "--config", str(cfg),
             "--workers", "2", "--port", "0"],
            env=_env(JAX_PLATFORMS="tpu,cpu"), cwd=REPO, text=True,
            capture_output=True, stdin=subprocess.DEVNULL, timeout=60,
        )
        assert r.returncode != 0
        assert "--workers 2" in r.stderr
        assert "chip belongs to one process" in r.stderr
        assert "Traceback" not in r.stderr


def test_host_only_broker_never_imports_a_backend():
    """Cluster workers run beside a device-matcher process. Such a broker (device
    matcher off) must never import jax — at the parent commit every
    Server enumerated jax.devices() for its stats plane."""
    code = """
import asyncio, sys
from mqtt_tpu.hooks.auth import AllowHook
from mqtt_tpu.listeners import Config
from mqtt_tpu.listeners.tcp import TCP
from mqtt_tpu.server import Options, Server

async def main():
    srv = Server(Options())
    srv.add_hook(AllowHook())
    srv.add_listener(TCP(Config(type="tcp", id="t", address="127.0.0.1:0")))
    await srv.serve()
    srv.publish_sys_topics()
    ok, _report = srv.health_report()
    await srv.close()
    assert ok
asyncio.run(main())
assert "jax" not in sys.modules, "host-only broker imported jax"
print("NO_BACKEND")
"""
    r = subprocess.run(
        [sys.executable, "-c", code], env=_env(JAX_PLATFORMS="tpu"),
        cwd=REPO, text=True, capture_output=True, timeout=120,
    )
    assert r.returncode == 0, r.stderr[-2000:]
    assert "NO_BACKEND" in r.stdout
