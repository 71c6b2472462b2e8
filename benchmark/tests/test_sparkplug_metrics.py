"""The three readers ``sparkplug-plant.fan-in`` brought, over slices made
by hand: what each divides by what, and nothing (None, never 0) from a
program whose snapshots lack the count: a parent commit."""

import pytest

import program_spans
from layer_metrics import (
    cork_early_write_share,
    cork_frames_per_write,
    delivery_route_share,
)


class Slice:
    def __init__(self, a, b):
        self.a, self.b, self.batches = a, b, []


# between the snapshots 40,000 deliveries left: 2,000 by the native
# flush, 36,000 into corks, 2,000 by the outbound queue; 400 corks were
# written with 38,400 packets in them (2,400 of them acks), 10 of them
# early
A = {"deliveries_flush": 100, "deliveries_cork": 5000, "deliveries_queue": 7,
     "cork_writes": 50, "cork_frames": 5100, "cork_early_writes": 1}
B = {"deliveries_flush": 2100, "deliveries_cork": 41000, "deliveries_queue": 2007,
     "cork_writes": 450, "cork_frames": 43500, "cork_early_writes": 11}


def without(snapshot, *keys):
    return {k: v for k, v in snapshot.items() if k not in keys}


@pytest.mark.parametrize(
    "reader, metric, a, b, want",
    [
        (delivery_route_share, "delivery_route_share.flush", A, B, 5.0),
        (delivery_route_share, "delivery_route_share.cork", A, B, 90.0),
        (delivery_route_share, "delivery_route_share.queue", A, B, 5.0),
        (cork_frames_per_write, "cork_frames_per_write", A, B, 96.0),
        (cork_early_write_share, "cork_early_write_share", A, B, 2.5),
        # a parent commit's snapshots hold no such count: nothing, not 0
        (delivery_route_share, "delivery_route_share.queue",
         without(A, "deliveries_queue"), without(B, "deliveries_queue"), None),
        (delivery_route_share, "delivery_route_share.cork",
         without(A, *A), without(B, *B), None),
        (cork_frames_per_write, "cork_frames_per_write",
         without(A, "cork_frames"), without(B, "cork_frames"), None),
        (cork_early_write_share, "cork_early_write_share",
         without(A, "cork_early_writes"), without(B, "cork_early_writes"), None),
        # nothing delivered, no cork written between the snapshots: nothing
        (delivery_route_share, "delivery_route_share.flush", A, A, None),
        (cork_frames_per_write, "cork_frames_per_write", A, A, None),
        (cork_early_write_share, "cork_early_write_share", A, A, None),
        # a route nothing took, a bound no cork reached: a reading, 0
        (delivery_route_share, "delivery_route_share.queue",
         A, {**B, "deliveries_queue": 7}, 0.0),
        (cork_early_write_share, "cork_early_write_share",
         A, {**B, "cork_early_writes": 1}, 0.0),
    ],
    ids=[
        "flush", "cork", "queue", "frames_per_write", "early_write_share",
        "parent_one_route", "parent_every_count", "parent_frames", "parent_early",
        "no_delivery", "no_cork_frames", "no_cork_early",
        "no_queued_delivery", "no_early_write",
    ],
)
def test_what_each_reader_reads(monkeypatch, reader, metric, a, b, want):
    monkeypatch.setattr(program_spans, "load", lambda: Slice(a, b))
    got = reader.read({"metric": metric, "trace": None})
    assert got == want if want is None else got == pytest.approx(want)


def test_the_three_routes_come_to_a_hundred(monkeypatch):
    monkeypatch.setattr(program_spans, "load", lambda: Slice(A, B))
    shares = [
        delivery_route_share.read({"metric": "delivery_route_share." + r, "trace": None})
        for r in delivery_route_share.ROUTES
    ]
    assert sum(shares) == pytest.approx(100.0)


@pytest.mark.parametrize(
    "reader, metric",
    [
        (delivery_route_share, "delivery_route_share.cork"),
        (cork_frames_per_write, "cork_frames_per_write"),
        (cork_early_write_share, "cork_early_write_share"),
    ],
    ids=lambda r: r if isinstance(r, str) else r.__name__.rpartition(".")[2],
)
def test_no_slice_reads_nothing(monkeypatch, reader, metric):
    monkeypatch.setattr(program_spans, "load", lambda: None)
    assert reader.read({"metric": metric, "trace": None}) is None
