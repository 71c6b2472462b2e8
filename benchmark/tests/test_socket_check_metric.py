"""``deliveries_per_socket_check`` over slices made by hand: what it
divides by what, and nothing (None, never 0) from a program whose
snapshots lack the count: a parent commit."""

import pytest

import program_spans
from layer_metrics import deliveries_per_socket_check


class Slice:
    def __init__(self, a, b):
        self.a, self.b, self.batches = a, b, []


# between the snapshots 120,000 deliveries left and fan-out read a
# socket 6,000 times: 117 completion slices of some 50 records each,
# and 150 deliveries that read their own
A = {"deliveries": 1000, "socket_checks": 900}
B = {"deliveries": 121000, "socket_checks": 6900}


@pytest.mark.parametrize(
    "a, b, want",
    [
        (A, B, 20.0),
        # every delivery read its socket: a count that rises with them
        (A, {"deliveries": 1500, "socket_checks": 1400}, 1.0),
        # a parent commit's snapshots hold no such count: nothing, not 0
        ({"deliveries": 1000}, {"deliveries": 121000}, None),
        (A, {"deliveries": 121000}, None),
        ({"socket_checks": 900}, {"socket_checks": 6900}, None),
        # no socket read between the snapshots: nothing
        (A, {**B, "socket_checks": 900}, None),
        (A, A, None),
        # sockets read and nothing delivered (every frame refused): 0
        (A, {**A, "socket_checks": 950}, 0.0),
    ],
    ids=[
        "records", "a_read_a_delivery", "parent", "parent_at_b", "no_deliveries_count",
        "no_socket_read", "empty_slice", "nothing_delivered",
    ],
)
def test_what_the_reader_reads(monkeypatch, a, b, want):
    monkeypatch.setattr(program_spans, "load", lambda: Slice(a, b))
    got = deliveries_per_socket_check.read(
        {"metric": "deliveries_per_socket_check", "trace": None}
    )
    assert got == want if want is None else got == pytest.approx(want)


def test_no_slice_reads_nothing(monkeypatch):
    monkeypatch.setattr(program_spans, "load", lambda: None)
    assert deliveries_per_socket_check.read(
        {"metric": "deliveries_per_socket_check", "trace": None}
    ) is None
