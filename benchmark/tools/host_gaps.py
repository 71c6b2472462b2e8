#!/usr/bin/env python3
"""What the host was doing while the chip was idle.

    python3 benchmark/tools/host_gaps.py <kept trace dir or .xplane.pb> [--top 10] [--json]

Input: a trace kept with ``benchmark/run.py --trace 1 --keep-trace <dir>``.
For each of the longest device-idle gaps (between two busy stretches of a
device plane's operations, as ``trace_reduce.py`` unions them) it lists
the program's own ``mqtt/*`` host annotations that overlap the gap, for
how long each, and with which ``batch`` numbers: the spans
``mqtt_tpu/tracing.py`` enters as ``jax.profiler.TraceAnnotation`` blocks
while a profiler session is live, which lie on the host planes of the
same file, on the device trace's own clock. ``breakdown.idle_gaps`` of a
result line can only name the operation that ENDED a gap; this names what
filled it. Time of a gap that no ``mqtt/*`` span covers is ``uncovered``:
the loop (ingest, fan-out), the waits between the spans, or nothing to do.

Reads the trace with nothing but JAX. Never touches a device.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import trace_reduce  # noqa: E402

PREFIX = "mqtt/"


def covered(intervals, lo: int, hi: int) -> int:
    """Length of the union of ``(start, end, ...)`` intervals, sorted by
    start, inside ``[lo, hi]``."""
    total = 0
    end = lo
    for s0, s1, *_ in intervals:
        s0, s1 = max(s0, end), min(s1, hi)
        if s1 > s0:
            total += s1 - s0
            end = s1
    return total


def busy_stretches(intervals) -> list:
    """``(start, end, name of the first operation)`` of each busy stretch
    of ``(start, end, name)`` intervals, in time order."""
    out = []
    for start, end, name in sorted(intervals):
        if out and start <= out[-1][1]:
            out[-1][1] = max(out[-1][1], end)
        else:
            out.append([start, end, name])
    return out


def idle_gaps(plane) -> list:
    """``(gap_start, gap_end, name of the operation that ended it)``."""
    intervals = [
        (int(ev.start_ns), int(ev.start_ns) + int(ev.duration_ns),
         trace_reduce.short(ev.name))
        for line in trace_reduce.op_lines(plane) for ev in line.events
    ]
    stretches = busy_stretches(intervals)
    return [
        (prev[1], nxt[0], nxt[2]) for prev, nxt in zip(stretches, stretches[1:])
    ]


def host_spans(profile) -> list:
    """``(start, end, name, batch, thread line)`` of every ``mqtt/*``
    annotation on a host plane."""
    out = []
    for plane in profile.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if not ev.name.startswith(PREFIX):
                    continue
                batch = None
                for key, value in ev.stats:
                    if key == "batch":
                        batch = int(value)
                start = int(ev.start_ns)
                out.append((start, start + int(ev.duration_ns), ev.name, batch,
                            line.name))
    return sorted(out)


def attribute(profile, top: int = 10) -> dict:
    spans = host_spans(profile)
    gaps = []
    for plane in profile.planes:
        if trace_reduce.DEVICE_PLANE.match(plane.name):
            gaps += [(g1 - g0, g0, g1, ended_by, plane.name)
                     for g0, g1, ended_by in idle_gaps(plane)]
    rows = []
    for length, g0, g1, ended_by, plane_name in sorted(gaps, reverse=True)[:top]:
        inside = [s for s in spans if s[0] < g1 and s[1] > g0]
        by_name: dict = {}
        for s0, s1, name, batch, _thread in inside:
            row = by_name.setdefault(name, {"ms": 0.0, "n": 0, "batches": set()})
            row["ms"] += (min(s1, g1) - max(s0, g0)) / 1e6
            row["n"] += 1
            if batch is not None:
                row["batches"].add(batch)
        rows.append({
            "gap_ms": length / 1e6, "device": plane_name,
            "ended_by": ended_by,
            "uncovered_ms": (length - covered(inside, g0, g1)) / 1e6,
            "host": {
                name: {"ms": round(r["ms"], 3), "n": r["n"],
                       "batches": sorted(r["batches"])}
                for name, r in sorted(by_name.items(), key=lambda kv: -kv[1]["ms"])
            },
        })
    return {
        "device_planes": sum(
            1 for p in profile.planes if trace_reduce.DEVICE_PLANE.match(p.name)
        ),
        "host_spans": len(spans),
        "span_names": sorted({s[2] for s in spans}),
        "gaps": rows,
    }


def find_trace(path: str) -> str:
    if os.path.isfile(path):
        return path
    found = sorted(
        glob.glob(os.path.join(path, "plugins", "profile", "*", "*.xplane.pb"))
        + glob.glob(os.path.join(path, "*.xplane.pb"))
    )
    if not found:
        raise SystemExit(f"host_gaps: no .xplane.pb under {path}")
    return found[-1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("trace")
    ap.add_argument("--top", type=int, default=10)
    ap.add_argument("--json", action="store_true")
    args = ap.parse_args(argv)
    from jax.profiler import ProfileData

    out = attribute(ProfileData.from_file(find_trace(args.trace)), args.top)
    if args.json:
        print(json.dumps(out))
        return 0
    print(f"{out['device_planes']} device plane(s), {out['host_spans']} mqtt/* host "
          f"spans: {', '.join(out['span_names']) or 'none'}")
    for i, g in enumerate(out["gaps"], 1):
        print(f"gap {i}: {g['gap_ms']:.3f} ms idle on {g['device']}, "
              f"ended by {g['ended_by'][:60]}")
        for name, r in g["host"].items():
            batches = ",".join(str(b) for b in r["batches"][:6])
            more = "..." if len(r["batches"]) > 6 else ""
            print(f"    {name:24s} {r['ms']:10.3f} ms  x{r['n']:<4d} batch {batches}{more}")
        print(f"    {'(no mqtt/* span)':24s} {g['uncovered_ms']:10.3f} ms")
    return 0


if __name__ == "__main__":
    sys.exit(main())
