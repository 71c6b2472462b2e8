"""What the program recorded of itself while the traced slice's profiler
session was live: ``mqtt_tpu.tracing.last_slice()`` — the span tree of
every device batch (``batches``, boundaries in ``perf_counter_ns``) between
two snapshots ``a`` and ``b`` (CPU per thread, topics matched, per-publish
loop counters, the loop heartbeat, full garbage collections). The program
arms itself on the session ``run.py --trace 1`` opens and freezes the slice
when it ends; the readers under ``layer_metrics/`` call in here afterwards.

A program that has no such record (a parent commit, ``--trace 0``) gives
``None`` from ``load()`` and every reader then leaves its metric out.
All arithmetic is here, beside the benchmark, over the slice's plain data.
"""

from __future__ import annotations

import json
import sys

_noted = False


def load():
    """The newest frozen slice of this process, or None."""
    try:
        from mqtt_tpu import tracing
    except ImportError:
        return None
    last_slice = getattr(tracing, "last_slice", None)
    sl = last_slice() if last_slice is not None else None
    if sl is not None:
        note(sl)
    return sl


def read_part(ctx, table, per: float):
    """What a reader of ``<metric>.<part>[.steady]`` returns: ``part``'s
    entry of ``table(slice)``, nanoseconds, in units of ``per`` ns; None
    where there is no slice or the slice has no such entry."""
    sl = load()
    if sl is None:
        return None
    ns = table(sl).get(ctx["metric"].split(".")[1])
    return None if ns is None else ns / per


def delta(sl, key):
    return sl.b[key] - sl.a[key]


def device_batches(sl) -> list:
    """The slice's batches that went to the device and came back whole."""
    return [
        r for r in sl.batches
        if r.deliver is not None and r.formed_ns is not None
        and r.h2d_dispatch is not None and r.d2h_sync is not None
        and r.resolve is not None and r.topics > 0
    ]


def publish_wait_ns(sl) -> dict:
    """Mean per publish, over the slice's batches, of the five stretches
    of its stay in the broker from ``submit()`` on: waiting for its
    batch to form (``stage``), formed to dispatch returned (``issue``),
    dispatch returned to sync done (``flight``), sync done to its future
    set (``resolve``), future set to flush done (``fanout``: waiting for
    the loop, then fanning out)."""
    recs = [r for r in device_batches(sl) if r.set_sum_ns]
    topics = sum(r.topics for r in recs)
    waited = sum(r.wait_n for r in recs)  # members parked inside the session
    out = {}
    if waited:
        out["stage"] = sum(r.wait_sum_ns for r in recs) / waited
    if topics:
        out["issue"] = sum(
            r.topics * (r.h2d_dispatch[1] - r.formed_ns) for r in recs
        ) / topics
        out["flight"] = sum(
            r.topics * (r.d2h_sync[1] - r.h2d_dispatch[1]) for r in recs
        ) / topics
        out["resolve"] = sum(
            r.set_sum_ns - r.topics * r.d2h_sync[1] for r in recs
        ) / topics
    n = delta(sl, "fanout_n")
    if n:
        out["fanout"] = (delta(sl, "fanout_wait_ns") + delta(sl, "fanout_busy_ns")) / n
    return out


BUSY = ("tokenize", "h2d_dispatch", "d2h_sync", "resolve")


def batch_busy_ns_per_pub(sl) -> dict:
    """Summed span time over the slice's batches / topics in them."""
    recs = device_batches(sl)
    topics = sum(r.topics for r in recs)
    if not topics:
        return {}
    return {
        slot: sum(getattr(r, slot)[1] - getattr(r, slot)[0] for r in recs) / topics
        for slot in BUSY
    }


def cpu_ns_per_pub(sl) -> dict:
    """Thread-group CPU between ``a`` and ``b`` / topics the matcher took
    in between them. A group that read under one tick of the kernel's
    accounting (10 ms) is left out: nothing was measured, not zero."""
    topics = delta(sl, "topics")
    if not topics:
        return {}
    return {g: ns / topics for g, ns in sl.cpu_ns_by_group().items() if ns > 0}


def loop_ns_per_pub(sl) -> dict:
    out = {}
    if delta(sl, "ingest_n"):
        out["ingest"] = delta(sl, "ingest_busy_ns") / delta(sl, "ingest_n")
    if delta(sl, "fanout_n"):
        out["fanout"] = delta(sl, "fanout_busy_ns") / delta(sl, "fanout_n")
    return out


def inflight_share(sl):
    """Share of ``a`` -> ``b`` with at least one batch between dispatch
    returned and sync done: what the host can see of the device's busy
    time, and so its ceiling. ``DeviceProfiler``'s own union of those
    windows (the fold behind ``duty_cycle``), read at both snapshots; a
    window counts where it closes."""
    wall_ns = delta(sl, "t_ns")
    if wall_ns <= 0 or not any(r.d2h_sync is not None for r in sl.batches):
        return None
    return 100.0 * delta(sl, "inflight_s") * 1e9 / wall_ns


def loop_stall_max_ns(sl):
    if delta(sl, "loop_beats") <= 0:
        return None  # no heartbeat ran: nothing was measured
    return sl.b["loop_stall_max_ns"]


def note(sl) -> None:
    """One line to stderr, once a process: what the per-layer metrics
    were folded from, unfolded (CPU by thread, pauses, counts)."""
    global _noted
    if _noted:
        return
    _noted = True
    before = sl.a["thread_cpu_ns"]
    threads = {
        name: round((ns - before.get(name, 0)) / 1e6, 3)
        for name, ns in sl.b["thread_cpu_ns"].items()
        if ns - before.get(name, 0) >= 500_000
    }
    recs = device_batches(sl)
    line = {
        "slice_s": round((sl.b["t_ns"] - sl.a["t_ns"]) / 1e9, 4),
        "batches_kept": len(sl.batches), "batches_whole": len(recs),
        "topics_in_batches": sum(r.topics for r in recs),
        "topics_a_to_b": delta(sl, "topics"),
        "process_cpu_ms": round(delta(sl, "process_cpu_ns") / 1e6, 3),
        "thread_cpu_ms": dict(sorted(threads.items(), key=lambda kv: -kv[1])),
        "fanout_n": delta(sl, "fanout_n"), "ingest_n": delta(sl, "ingest_n"),
        "fanout_wait_ms_mean": round(
            delta(sl, "fanout_wait_ns") / max(1, delta(sl, "fanout_n")) / 1e6, 4),
        "gen2_pauses_ms": [round(d / 1e6, 3) for _end, d in sl.gen2_pauses()],
        # since the process began (set-up's load included): the newest
        # full collections and how long before ``b`` each ended
        "gen2_before_b": {
            "newest_ms": [round(d / 1e6, 1) for _end, d in sl.b["gc2_recent"][-5:]],
            "newest_ended_s_before_b": [
                round((sl.b["t_ns"] - end) / 1e9, 1)
                for end, _d in sl.b["gc2_recent"][-5:]
            ],
        },
        "loop_stall_max_ms": round(sl.b["loop_stall_max_ns"] / 1e6, 3),
    }
    print("# program spans: " + json.dumps(line), file=sys.stderr, flush=True)
