"""The readers the loop's ledger brought, over slices made by hand: the
arithmetic where the keys are there, and nothing (None, never 0) from a
program whose snapshots lack them: a parent commit, or a loop with no
selector to frame."""

import pytest
from mqtt_tpu.tracing import TraceSlice

import loop_ledger
import program_spans
from layer_metrics import (
    gc_young_pause_max_ms,
    loop_busy_share,
    loop_events_per_poll,
    loop_ledger_us_per_pub,
    loop_polls_per_pub,
    loop_stall_split_ms,
    loop_us_per_send,
    setup_build_s,
    setup_load_s,
    slice_flush_share,
    socket_reads_per_pub,
    socket_sends_per_pub,
)

MS = 1_000_000


class OldSlice:
    """A parent commit's slice: two snapshots, and no ``young_pauses``."""

    def __init__(self, a, b):
        self.a, self.b, self.batches = a, b, []


class Slice(OldSlice):
    young_pauses = TraceSlice.young_pauses  # the program's own pick


# a 3 s slice of one loop, 10,000 topics: 1.2 s of iterations (0.3 ingest,
# 0.1 acks, 0.5 fan-out of which 0.2 the slices' flushes, so 0.3 of rest),
# 20,000 polls that could not block at 5 us, 600 that could (1.7 s idle)
A = {
    "t_ns": 50_000 * MS, "topics": 1_000,
    "iter_busy_ns": 7 * MS, "poll0_n": 100, "poll0_ns": 1 * MS,
    "pollw_n": 10, "pollw_ns": 2 * MS, "poll_ready_n": 50, "stall": None,
    "ingest_busy_ns": 0, "ack_busy_ns": 0, "fanout_busy_ns": 10 * MS,
    "slice_flush_ns": 0, "send_busy_ns": 5 * MS,
    "socket_sends": 100, "socket_reads": 40,
    "gc_pause_ns": [10 * MS, 0, 900 * MS],
    "young_recent": [(49_000 * MS, 9 * MS, 0)],
    "bulk_load_seconds": 22.5, "bulk_build_seconds": 6.25,
}
B = {
    "t_ns": 53_000 * MS, "topics": 11_000,
    "iter_busy_ns": 1_207 * MS, "poll0_n": 20_100, "poll0_ns": 101 * MS,
    "pollw_n": 610, "pollw_ns": 1_702 * MS, "poll_ready_n": 9_050,
    "stall": {
        "t0_ns": 51_000 * MS, "busy_ns": 480 * MS, "ingest_ns": 20 * MS,
        "ack_ns": 10 * MS, "fanout_ns": 50 * MS, "flush_ns": 5 * MS,
        "send_ns": 8 * MS, "gc_ns": 390 * MS, "gc_gen": 1,
    },
    "ingest_busy_ns": 300 * MS, "ack_busy_ns": 100 * MS, "fanout_busy_ns": 510 * MS,
    "slice_flush_ns": 200 * MS, "send_busy_ns": 255 * MS,
    "socket_sends": 600, "socket_reads": 9_040,
    "gc_pause_ns": [30 * MS, 390 * MS, 900 * MS],
    "young_recent": [
        (49_000 * MS, 9 * MS, 0), (50_500 * MS, 12 * MS, 0), (51_400 * MS, 390 * MS, 1),
    ],
    "bulk_load_seconds": 22.5, "bulk_build_seconds": 6.25,
}
FRAME = loop_ledger.FRAME + ("poll_ready_n", "stall")


def without(snapshot, *keys):
    return {k: v for k, v in snapshot.items() if k not in keys}


CASES = {
    # 1 - 1.7 s idle of 3 s
    "loop_busy_share": (loop_busy_share, "loop_busy_share.steady", 100 * (1 - 1.7 / 3)),
    # 100 ms of polls that could not block, and 600 more calls at their 5 us
    "poll": (loop_ledger_us_per_pub, "loop_ledger_us_per_pub.poll", (100_000 + 600 * 5) / 10_000),
    "send": (loop_ledger_us_per_pub, "loop_ledger_us_per_pub.send.steady", 25.0),
    "gc": (loop_ledger_us_per_pub, "loop_ledger_us_per_pub.gc", 41.0),
    "rest": (loop_ledger_us_per_pub, "loop_ledger_us_per_pub.rest.steady", 30.0),
    "loop_polls_per_pub": (loop_polls_per_pub, "loop_polls_per_pub", 2.06),
    # 9,000 ready sockets over 20,600 polls
    "loop_events_per_poll": (loop_events_per_poll, "loop_events_per_poll.steady", 9_000 / 20_600),
    "socket_reads_per_pub": (socket_reads_per_pub, "socket_reads_per_pub.steady", 0.9),
    "socket_sends_per_pub": (socket_sends_per_pub, "socket_sends_per_pub.steady", 0.05),
    "loop_us_per_send": (loop_us_per_send, "loop_us_per_send", 500.0),
    "slice_flush_share": (slice_flush_share, "slice_flush_share", 40.0),
    "gc_young_pause_max_ms": (gc_young_pause_max_ms, "gc_young_pause_max_ms.steady", 390.0),
    "stall_busy": (loop_stall_split_ms, "loop_stall_split_ms.busy.steady", 480.0),
    "stall_ingest": (loop_stall_split_ms, "loop_stall_split_ms.ingest", 30.0),
    "stall_fanout": (loop_stall_split_ms, "loop_stall_split_ms.fanout", 50.0),
    "stall_gc": (loop_stall_split_ms, "loop_stall_split_ms.gc.steady", 390.0),
    "setup_load_s": (setup_load_s, "setup_load_s", 22.5),
    "setup_build_s": (setup_build_s, "setup_build_s", 6.25),
}
# the key whose absence silences the reader (a parent commit lacks all)
NEEDS = {
    "loop_busy_share": "pollw_ns", "poll": "poll0_n", "send": "send_busy_ns",
    "gc": "gc_pause_ns", "rest": "iter_busy_ns", "loop_polls_per_pub": "pollw_n",
    "loop_events_per_poll": "poll_ready_n",
    "socket_reads_per_pub": "socket_reads", "socket_sends_per_pub": "socket_sends",
    "loop_us_per_send": "send_busy_ns", "slice_flush_share": "slice_flush_ns",
    "gc_young_pause_max_ms": "young_recent", "stall_busy": "stall",
    "stall_ingest": "stall", "stall_fanout": "stall", "stall_gc": "stall",
    "setup_load_s": "bulk_load_seconds", "setup_build_s": "bulk_build_seconds",
}


def read(monkeypatch, name, a, b, slice_class=None):
    reader, metric, _want = CASES[name]
    make = Slice if slice_class is None else slice_class
    monkeypatch.setattr(program_spans, "load", lambda: make(a, b))
    monkeypatch.setattr(loop_ledger, "_noted", True)
    return reader.read({"metric": metric, "trace": None})


@pytest.mark.parametrize("name", sorted(CASES))
def test_what_each_reader_reads(monkeypatch, name):
    assert read(monkeypatch, name, A, B) == pytest.approx(CASES[name][2])


@pytest.mark.parametrize("name", sorted(CASES))
def test_a_slice_that_lacks_the_keys_reads_nothing(monkeypatch, name):
    # a parent commit: PR 34's snapshots
    old = ("t_ns", "topics", "ingest_busy_ns", "ack_busy_ns", "fanout_busy_ns")
    parent_a = {k: A[k] for k in old}
    parent_b = {k: B[k] for k in old}
    if name != "socket_sends_per_pub":  # PR 27's count: the parent has it
        assert read(monkeypatch, name, parent_a, parent_b, OldSlice) is None
    # this program with the one key gone (a loop with no selector to
    # frame leaves the frame's out)
    key = NEEDS[name]
    assert read(monkeypatch, name, without(A, key), without(B, key)) is None
    # and no slice at all
    monkeypatch.setattr(program_spans, "load", lambda: None)
    assert CASES[name][0].read({"metric": CASES[name][1], "trace": None}) is None


def test_nothing_to_divide_by_reads_nothing_and_no_collection_reads_zero(monkeypatch):
    same_topics = {**B, "topics": A["topics"]}
    for name in ("poll", "rest", "loop_polls_per_pub", "socket_reads_per_pub"):
        assert read(monkeypatch, name, A, same_topics) is None
    assert read(monkeypatch, "loop_us_per_send", A, {**B, "socket_sends": 100}) is None
    assert read(monkeypatch, "slice_flush_share", A, {**B, "fanout_busy_ns": 10 * MS}) is None
    # no poll that could not block: the calls' price is unknown
    assert read(monkeypatch, "poll", A, {**B, "poll0_n": 100}) is None
    # no young collection inside the slice, none in the stall: 0, a number
    quiet = {**B, "young_recent": A["young_recent"], "stall": {**B["stall"], "gc_ns": 0}}
    assert read(monkeypatch, "gc_young_pause_max_ms", A, quiet) == 0.0
    assert read(monkeypatch, "stall_gc", A, quiet) == 0.0
    # a program that keeps the list and cannot pick from it: nothing
    assert read(monkeypatch, "gc_young_pause_max_ms", A, B, OldSlice) is None
    # a loop that never turned
    still = {**B, "poll0_n": A["poll0_n"], "pollw_n": A["pollw_n"]}
    assert read(monkeypatch, "loop_events_per_poll", A, still) is None


def test_the_note_line_unfolds_the_ledger_once(monkeypatch, capsys):
    monkeypatch.setattr(program_spans, "load", lambda: Slice(A, B))
    monkeypatch.setattr(program_spans, "_noted", True)
    monkeypatch.setattr(loop_ledger, "_noted", False)
    assert loop_ledger.load(*loop_ledger.FRAME) is not None
    assert loop_ledger.load() is not None
    lines = [
        ln for ln in capsys.readouterr().err.splitlines() if ln.startswith("# loop ledger: ")
    ]
    assert len(lines) == 1
    import json

    line = json.loads(lines[0].split(": ", 1)[1])
    assert line["parts_over_frame"] == pytest.approx(1.0)
    assert line["stall"]["busy_ms"] == 480.0 and line["stall"]["gc_gen"] == 1
    assert line["stall"]["t0_ms"] == 1_000.0  # after snapshot A
    assert line["gc_ms"] == [20.0, 390.0, 0.0]
    assert line["young_pauses_ms"] == [[12.0, 0], [390.0, 1]]
    assert line["socket_reads"] == 9_000 and line["poll_ready_n"] == 9_000
