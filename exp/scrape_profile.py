#!/usr/bin/env python
"""CI profile-scrape gate: boot a broker with the host observatory on,
drive a 100-client stress burst over real TCP (the client count ROADMAP
item 3's collapse is measured at), fetch ``GET /profile`` from the
stats listener, validate the collapsed export with the pure-Python
checker (mqtt_tpu.profiling.check_collapsed) and the ``?format=trace``
export with the trace-event checker, assert the lock plane and the
fan-out amplification counters actually populated on /metrics, and
write the collapsed snapshot to disk — the workflow uploads it as an
artifact, so every CI run carries a flamegraph of its own burst.

Usage: python exp/scrape_profile.py [--out profile-snapshot.txt]
Exits non-zero when an export fails to parse or the expected signals
are missing.
"""

import argparse
import asyncio
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from scrapelib import http_get as _http_get  # noqa: E402


async def main(out_path: str) -> int:
    from mqtt_tpu.hooks.auth import AllowHook
    from mqtt_tpu.listeners import Config as LConfig, HTTPStats
    from mqtt_tpu.listeners.tcp import TCP
    from mqtt_tpu.profiling import check_collapsed
    from mqtt_tpu.server import Options, Server
    from mqtt_tpu.stress import run_stress
    from mqtt_tpu.tracing import check_trace_events

    opts = Options(
        device_matcher=False,  # the HOST path is what this gate profiles
        telemetry_sample=1,
        profile_hz=97.0,  # a short burst must still land plenty of sweeps
        # broker and load generator share one process+loop here, so the
        # generator's own starved reads would trip the governor; this
        # gate validates the profile plane, not overload control
        overload_control=False,
    )
    srv = Server(opts)
    srv.add_hook(AllowHook())
    srv.add_listener(TCP(LConfig(type="tcp", id="t", address="127.0.0.1:0")))
    srv.add_listener(
        HTTPStats(
            LConfig(type="sysinfo", id="s", address="127.0.0.1:0"),
            srv.info,
            telemetry=srv.telemetry,
        )
    )
    await srv.serve()
    try:
        host, port = srv.listeners.get("t").address().rsplit(":", 1)
        burst = await run_stress("127.0.0.1", int(port), 100, 60)
        print(f"# burst: {burst['aggregate_msgs_per_sec']} msgs/s", file=sys.stderr)
        stats_addr = srv.listeners.get("s").address()

        head, body = await _http_get(stats_addr, "/profile")
        assert b"200" in head.split(b"\r\n", 1)[0], head
        collapsed = body.decode()
        stacks = check_collapsed(collapsed)

        head, body = await _http_get(stats_addr, "/profile?format=trace")
        assert b"200" in head.split(b"\r\n", 1)[0], head
        events = check_trace_events(json.loads(body.decode()))

        head, body = await _http_get(stats_addr, "/metrics")
        assert b"200" in head.split(b"\r\n", 1)[0], head
        text = body.decode()
        missing = [
            m
            for m in (
                "mqtt_tpu_profile_samples_total",
                "mqtt_tpu_lock_acquisitions_total",
                "mqtt_tpu_publish_encodes_total",
                "mqtt_tpu_fanout_amplification_ratio",
            )
            if m not in text
        ]
        if missing:
            print(f"FAIL: /metrics missing {missing}", file=sys.stderr)
            return 1
        # the 100-client burst MUST have exercised the instrumented
        # locks — a silent lock-plane regression would otherwise pass
        clients_acq = 0
        for line in text.splitlines():
            if line.startswith('mqtt_tpu_lock_acquisitions_total{lock="clients"}'):
                clients_acq = int(float(line.rsplit(" ", 1)[1]))
        if clients_acq <= 0:
            print("FAIL: clients lock saw no acquisitions", file=sys.stderr)
            return 1

        amp = round(
            srv.telemetry.fanout_deliveries.value
            / max(1, srv.info.messages_received),
            4,
        )
        with open(out_path, "w") as f:
            f.write(collapsed)
        print(
            f"OK: {stacks} collapsed stacks, {events} trace events, "
            f"clients-lock acquisitions={clients_acq}, "
            f"delivery amplification={amp}; snapshot -> {out_path}",
            file=sys.stderr,
        )
        return 0
    finally:
        await srv.close()


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="profile-snapshot.txt")
    sys.exit(asyncio.run(main(ap.parse_args().out)))
