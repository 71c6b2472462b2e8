"""The event loop's own account of the traced slice, read from the two
snapshots of ``mqtt_tpu.tracing.last_slice()`` (``program_spans.load``).

While the slice's profiler session is live the program stands a timing
frame around its loop's selector (``tracing._LoopFrame``) and books every
iteration: ``iter_busy_ns`` (the summed iterations, each from one
``select()``'s return to the next call), ``poll0_n`` / ``poll0_ns``
(``select()`` calls that could not block: the pure cost of the system
call), ``pollw_n`` / ``pollw_ns`` (calls that could: the loop's idle time,
plus the call), ``poll_ready_n`` (events returned) and ``stall`` (the
longest iteration with the phase counters' deltas across it). The three
times come to B − A. Beside them: ``send_busy_ns`` around the calls that
count ``socket_sends``, ``slice_flush_ns`` (the joined writes at the
completion slices' ends, inside ``fanout_busy_ns``), ``socket_reads``
(read loops' wake-ups on data), and the collector's ``gc_pause_ns`` by
generation with the slice's ``young_pauses()``.

A program whose snapshots lack a key (a parent commit, a loop with no
selector to frame) reads nothing: every function here returns None for it.
All arithmetic is here, beside the benchmark, over the slice's plain data.
"""

from __future__ import annotations

import json
import sys

import program_spans

FRAME = ("poll0_n", "poll0_ns", "pollw_n", "pollw_ns", "iter_busy_ns")
delta = program_spans.delta
_noted = False


def has(sl, *keys) -> bool:
    return all(k in sl.a and k in sl.b for k in keys)


def load(*keys):
    """The newest slice if both its snapshots hold ``keys``, else None."""
    sl = program_spans.load()
    if sl is None or not has(sl, *keys):
        return None
    note(sl)
    return sl


def gc_pause_ns(sl) -> int:
    """Collections of every generation between the snapshots, summed."""
    return sum(b - a for a, b in zip(sl.a["gc_pause_ns"], sl.b["gc_pause_ns"]))


def per_pub_ns(sl) -> dict:
    """The loop's time a topic the matcher took in, by what it went on:
    ``poll`` (the ``select()`` calls themselves: those that could not
    block, and the same price for each that could), ``send`` and ``gc``
    (both lie inside ingest, fan-out or the rest: an "of which"), and
    ``rest``: the iterations less ingest, acks and fan-out: asyncio's
    transport reads, task steps, timers, waits for the interpreter lock."""
    topics = delta(sl, "topics")
    if not topics:
        return {}
    out = {
        "rest": (
            delta(sl, "iter_busy_ns") - delta(sl, "ingest_busy_ns")
            - delta(sl, "ack_busy_ns") - delta(sl, "fanout_busy_ns")
        ) / topics,
    }
    poll0_n = delta(sl, "poll0_n")
    if poll0_n:
        poll0_ns = delta(sl, "poll0_ns")
        out["poll"] = (
            poll0_ns + delta(sl, "pollw_n") * poll0_ns / poll0_n
        ) / topics
    if has(sl, "send_busy_ns"):
        out["send"] = delta(sl, "send_busy_ns") / topics
    if has(sl, "gc_pause_ns"):
        out["gc"] = gc_pause_ns(sl) / topics
    return out


def young_pauses(sl):
    """``(end_ns, ns, generation)`` of the collections of generations 0
    and 1 of over a millisecond that ended between the snapshots, as the
    program's slice picks them (``TraceSlice.young_pauses``); None where
    the program has no such list."""
    pick = getattr(sl, "young_pauses", None)
    return None if pick is None or "young_recent" not in sl.b else pick()


def stall_split_ns(sl) -> dict:
    """The slice's longest iteration: its length, and what of it was a
    read's frame loop (``ingest``, with the acks), fan-out (the slice's
    joined writes are inside it) and collections (inside either, or the
    rest). What is left of ``busy`` is the iteration's unnamed rest."""
    stall = sl.b.get("stall")
    if not stall:
        return {}
    return {
        "busy": stall["busy_ns"],
        "ingest": stall["ingest_ns"] + stall["ack_ns"],
        "fanout": stall["fanout_ns"],
        "gc": stall["gc_ns"],
    }


def note(sl) -> None:
    """One line to stderr, once a process: the ledger unfolded."""
    global _noted
    if _noted:
        return
    _noted = True

    def ms(ns):
        return round(ns / 1e6, 3)

    line = {"slice_ms": ms(delta(sl, "t_ns")), "topics": delta(sl, "topics")}
    if has(sl, *FRAME):
        parts = delta(sl, "iter_busy_ns") + delta(sl, "poll0_ns") + delta(sl, "pollw_ns")
        line.update({
            "iter_busy_ms": ms(delta(sl, "iter_busy_ns")),
            "poll0_n": delta(sl, "poll0_n"), "poll0_ms": ms(delta(sl, "poll0_ns")),
            "pollw_n": delta(sl, "pollw_n"), "pollw_ms": ms(delta(sl, "pollw_ns")),
            "poll_ready_n": delta(sl, "poll_ready_n"),
            "parts_over_frame": round(parts / max(1, delta(sl, "t_ns")), 5),
            # in ms, its start as ms after snapshot A
            "stall": sl.b.get("stall") and {
                k[:-3] + "_ms" if k.endswith("_ns") else k:
                v if k == "gc_gen" else ms(v - sl.a["t_ns"] if k == "t0_ns" else v)
                for k, v in sl.b["stall"].items()
            },
        })
    for key in ("ingest_busy_ns", "ack_busy_ns", "fanout_busy_ns", "slice_flush_ns",
                "send_busy_ns"):
        if has(sl, key):
            line[key[:-3] + "_ms"] = ms(delta(sl, key))
    for key in ("socket_reads", "socket_sends"):
        if has(sl, key):
            line[key] = delta(sl, key)
    if has(sl, "gc_pause_ns"):
        line["gc_ms"] = [
            ms(b - a) for a, b in zip(sl.a["gc_pause_ns"], sl.b["gc_pause_ns"])
        ]
    if young_pauses(sl) is not None:
        line["young_pauses_ms"] = [[ms(ns), gen] for _end, ns, gen in young_pauses(sl)]
        # the hook is always on: the newest young collections since the
        # process began, the window's among them, as (ms, generation,
        # seconds before snapshot B)
        line["young_before_b"] = [
            [ms(ns), gen, round((sl.b["t_ns"] - end) / 1e9, 1)]
            for end, ns, gen in sl.b["young_recent"][-24:]
        ]
    for key in ("bulk_load_seconds", "bulk_build_seconds"):
        if key in sl.b:
            line[key] = round(sl.b[key], 3)
    print("# loop ledger: " + json.dumps(line), file=sys.stderr, flush=True)
