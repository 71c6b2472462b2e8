"""``qos_begin_us_per_delivery`` and ``qos_hook_dispatch_share`` over
slices made by hand: the bookkeeping's time and the hooked begins over
the QoS>0 deliveries begun, and nothing (None, never 0) from a program
whose snapshots lack the counts (a parent commit) or from a slice in
which no QoS>0 delivery was begun."""

import pytest

import program_spans
from layer_metrics import qos_begin_us_per_delivery, qos_hook_dispatch_share


class Slice:
    def __init__(self, a, b):
        self.a, self.b, self.batches = a, b, []


# between the snapshots 40,000 deliveries were begun in 120 ms, none hooked
A = {"qos_begins": 1000, "qos_begins_hooked": 0, "qos_begin_ns": 0}
B = {"qos_begins": 41000, "qos_begins_hooked": 0, "qos_begin_ns": 120_000_000}


def read(reader):
    return reader.read({"metric": reader.__name__.split(".")[-1], "trace": None})


@pytest.mark.parametrize(
    "a, b, want",
    [
        (A, B, 3.0),
        # time is added while a profiler session is live only
        (A, {**B, "qos_begin_ns": 0}, 0.0),
        # a parent commit's snapshots hold none of the counts: nothing
        ({}, {}, None),
        ({"qos_begins": 1000}, {"qos_begins": 41000}, None),
        # a QoS0 cell: no delivery begun, nothing
        (A, A, None),
    ],
    ids=["broadcast", "unarmed", "parent", "no_time", "qos0"],
)
def test_time_a_delivery(monkeypatch, a, b, want):
    monkeypatch.setattr(program_spans, "load", lambda: Slice(a, b))
    got = read(qos_begin_us_per_delivery)
    assert got == want if want is None else got == pytest.approx(want)


@pytest.mark.parametrize(
    "a, b, want",
    [
        (A, B, 0.0),
        # a storage hook sees every new entry
        (A, {**B, "qos_begins_hooked": 40000}, 100.0),
        (A, {**B, "qos_begins_hooked": 10000}, 25.0),
        ({}, {}, None),
        ({"qos_begins": 1000}, {"qos_begins": 41000}, None),
        (A, A, None),
    ],
    ids=["no_hook", "storage_hook", "quarter", "parent", "no_hooked_count", "qos0"],
)
def test_hooked_share(monkeypatch, a, b, want):
    monkeypatch.setattr(program_spans, "load", lambda: Slice(a, b))
    got = read(qos_hook_dispatch_share)
    assert got == want if want is None else got == pytest.approx(want)


@pytest.mark.parametrize(
    "reader", [qos_begin_us_per_delivery, qos_hook_dispatch_share]
)
def test_no_slice_reads_nothing(monkeypatch, reader):
    monkeypatch.setattr(program_spans, "load", lambda: None)
    assert read(reader) is None
