"""From the profiler's ``.xplane.pb`` to device busy time and kernel times.

Reads the trace with nothing but JAX (``jax.profiler.ProfileData``). A
device plane is one named ``/device:TPU:<n>``; on it the line
``XLA Ops`` holds one event per operation that ran (where a backend
names its lines otherwise, every line but the step and module summaries
counts). Busy is the UNION of those events' intervals, per device, then
the mean over devices: two overlapping operations are not counted twice,
which is why a share of the window can never pass 100%.
"""

from __future__ import annotations

import glob
import os
import re

# "/device:TPU:0"; not "/device:CUSTOM:Megascale Trace", which holds no chip
DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+")

SUMMARY_LINES = ("Steps", "XLA Modules", "XLA TraceMe", "Framework Ops")


def union_seconds(intervals) -> tuple:
    """``(busy_ns, gaps)`` for ``(start, end, name)`` intervals: the
    length of their union, and each idle gap between two busy stretches
    as ``(gap_ns, name of the operation that ended it)``."""
    busy = 0
    gaps = []
    cur_start = cur_end = None
    for start, end, name in sorted(intervals):
        if cur_end is None:
            cur_start, cur_end = start, end
        elif start <= cur_end:
            cur_end = max(cur_end, end)
        else:
            busy += cur_end - cur_start
            gaps.append((start - cur_end, name))
            cur_start, cur_end = start, end
    if cur_end is not None:
        busy += cur_end - cur_start
    return busy, gaps


def short(name: str, most: int = 96) -> str:
    """An HLO instruction's text runs to thousands of characters: keep
    its head, which names the instruction, its shape and its kind."""
    return name if len(name) <= most else name[: most - 3] + "..."


def op_lines(plane):
    lines = list(plane.lines)
    named = [ln for ln in lines if ln.name == "XLA Ops"]
    return named or [ln for ln in lines if ln.name not in SUMMARY_LINES]


def reduce_profile(profile) -> dict:
    """``busy_s`` (mean over device planes), ``device_ops`` as
    ``[name, seconds]`` by time spent, ``idle_gaps`` as ``[what ended the
    gap, seconds]`` longest first, and ``kernels``: seconds per jitted
    program (the ``XLA Modules`` line), which is how ``flat_match_*`` is
    found whatever operations it is lowered to."""
    planes = [p for p in profile.planes if DEVICE_PLANE.match(p.name)]
    busy_ns = []
    ops: dict = {}
    kernels: dict = {}
    all_gaps = []
    for plane in planes:
        intervals = []
        for line in op_lines(plane):
            for ev in line.events:
                start = int(ev.start_ns)
                end = start + int(ev.duration_ns)
                name = short(ev.name)
                intervals.append((start, end, name))
                ops[name] = ops.get(name, 0) + end - start
        for line in plane.lines:
            if line.name == "XLA Modules":
                for ev in line.events:
                    kernels[ev.name] = kernels.get(ev.name, 0) + int(ev.duration_ns)
        busy, gaps = union_seconds(intervals)
        busy_ns.append(busy)
        all_gaps += gaps
    n = max(1, len(planes))
    top = sorted(ops.items(), key=lambda kv: -kv[1])
    return {
        "device_planes": len(planes),
        "busy_s": sum(busy_ns) / n / 1e9,
        "device_ops": [[name, ns / 1e9 / n] for name, ns in top],
        "idle_gaps": [
            [f"before {name}", ns / 1e9]
            for ns, name in sorted(all_gaps, reverse=True)[:10]
        ],
        "kernels": {name: ns / 1e9 / n for name, ns in kernels.items()},
    }


def reduce_dir(trace_dir: str) -> dict:
    from jax.profiler import ProfileData

    found = sorted(
        glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb"))
    )
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return reduce_profile(ProfileData.from_file(found[-1]))
