"""The reader the direct feeder brought, over slices made by hand: the
quotient where both counts are there, and nothing (None, never 0) from a
program whose snapshots lack one: a parent commit."""

import pytest

import program_spans
from layer_metrics import direct_read_share


class Slice:
    def __init__(self, a, b):
        self.a, self.b, self.batches = a, b, []


# 2,000 wake-ups on data between the snapshots, 1,900 of them the
# broker's own protocol's (a WebSocket connection made the rest)
A = {"socket_reads": 500, "direct_reads": 400}
B = {"socket_reads": 2500, "direct_reads": 2300}


def without(snapshot, *keys):
    return {k: v for k, v in snapshot.items() if k not in keys}


@pytest.mark.parametrize(
    "a, b, want",
    [
        (A, B, 95.0),
        # every connection on the direct feeder: the cells' reading
        (A, {"socket_reads": 2500, "direct_reads": 2400}, 100.0),
        # every connection on the stream feeder (scan_coalesce): 0, a number
        (A, {**B, "direct_reads": 400}, 0.0),
        # a parent commit: socket_reads alone (PR 35), or neither
        (without(A, "direct_reads"), without(B, "direct_reads"), None),
        ({}, {}, None),
        # one snapshot short of a key
        (A, without(B, "direct_reads"), None),
        (without(A, "socket_reads"), B, None),
        # no read between the snapshots: nothing to divide by
        (A, A, None),
    ],
    ids=[
        "share", "all_direct", "all_stream", "parent_with_reads", "parent",
        "b_lacks_direct", "a_lacks_reads", "no_read",
    ],
)
@pytest.mark.parametrize("metric", ["direct_read_share", "direct_read_share.steady"])
def test_what_the_reader_reads(monkeypatch, metric, a, b, want):
    monkeypatch.setattr(program_spans, "load", lambda: Slice(a, b))
    got = direct_read_share.read({"metric": metric, "trace": None})
    assert got == want if want is None else got == pytest.approx(want)


def test_no_slice_reads_nothing(monkeypatch):
    monkeypatch.setattr(program_spans, "load", lambda: None)
    assert direct_read_share.read({"metric": "direct_read_share", "trace": None}) is None
