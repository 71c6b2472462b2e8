"""The four readers ``fanout-5-1000.broadcast`` brought, over slices made
by hand: what each divides by what, and nothing (None, never 0) from a
program whose snapshots lack the count: a parent commit."""

import pytest

import program_spans
from layer_metrics import (
    deliveries_per_pub,
    fanout_us_per_delivery,
    slice_targets_max,
    wide_resolved_share,
)


class Slice:
    def __init__(self, a, b):
        self.a, self.b, self.batches = a, b, []


# 80 topics matched and 80 publishes fanned out between the snapshots,
# each delivered to 1,000 sockets, in 2.4 s of fan-out; 79 of the answers
# held a wide entry's hit
A = {"topics": 1000, "fanout_n": 0, "deliveries": 5000, "fanout_busy_ns": 10**9,
     "wide_topics": 40, "slice_targets_max": 8000}
B = {"topics": 1080, "fanout_n": 80, "deliveries": 85000,
     "fanout_busy_ns": 34 * 10**8, "wide_topics": 119, "slice_targets_max": 40000}


def without(snapshot, *keys):
    return {k: v for k, v in snapshot.items() if k not in keys}


@pytest.mark.parametrize(
    "reader, a, b, want",
    [
        (deliveries_per_pub, A, B, 1000.0),
        (fanout_us_per_delivery, A, B, 30.0),
        (wide_resolved_share, A, B, 98.75),
        (slice_targets_max, A, B, 40000),
        # a parent commit's snapshots hold no such count: nothing, not 0
        (deliveries_per_pub, without(A, "deliveries"), without(B, "deliveries"), None),
        (fanout_us_per_delivery, without(A, "deliveries"), without(B, "deliveries"), None),
        (wide_resolved_share, without(A, "wide_topics"), without(B, "wide_topics"), None),
        (slice_targets_max, without(A, "slice_targets_max"),
         without(B, "slice_targets_max"), None),
        # a batch resolved in the slice and fanned out after it: still 1,000
        (deliveries_per_pub, A, {**B, "topics": 1120}, 1000.0),
        # nothing matched, nothing delivered between the snapshots: nothing
        (deliveries_per_pub, A, A, None),
        (fanout_us_per_delivery, A, A, None),
        (wide_resolved_share, A, A, None),
        # a table without a wide entry is a reading: 0
        (wide_resolved_share, {**A, "wide_topics": 0}, {**B, "wide_topics": 0}, 0.0),
    ],
    ids=[
        "deliveries_per_pub", "fanout_us_per_delivery", "wide_resolved_share",
        "slice_targets_max", "parent_deliveries", "parent_fanout_us",
        "parent_wide", "parent_slice_max", "resolve_is_not_fan_out", "no_topic",
        "no_delivery",
        "no_topic_wide", "no_wide_entry",
    ],
)
def test_what_each_reader_reads(monkeypatch, reader, a, b, want):
    monkeypatch.setattr(program_spans, "load", lambda: Slice(a, b))
    got = reader.read({"metric": reader.__name__.rpartition(".")[2], "trace": None})
    assert got == want if want is None else got == pytest.approx(want)


@pytest.mark.parametrize(
    "reader",
    [deliveries_per_pub, fanout_us_per_delivery, wide_resolved_share, slice_targets_max],
    ids=lambda r: r.__name__.rpartition(".")[2],
)
def test_no_slice_reads_nothing(monkeypatch, reader):
    monkeypatch.setattr(program_spans, "load", lambda: None)
    assert reader.read({"metric": "x", "trace": None}) is None
