"""``qos_run_share`` and ``qos_entries_per_run`` over slices made by
hand: the run-settled QoS>0 deliveries over those begun, and over the
runs, and nothing (None, never 0) from a program whose snapshots lack
the counts (a parent commit) or from a slice with nothing to divide by."""

import pytest

import program_spans
from layer_metrics import qos_entries_per_run, qos_run_share


class Slice:
    def __init__(self, a, b):
        self.a, self.b, self.batches = a, b, []


# between the snapshots 40,000 deliveries were begun, 39,960 of them in
# 1,000 runs of a socket's 40 less the 40 of one socket begun one by one
A = {"qos_begins": 1000, "qos_runs": 25, "qos_run_entries": 1000}
B = {"qos_begins": 41000, "qos_runs": 1024, "qos_run_entries": 40960}


def read(reader):
    return reader.read({"metric": reader.__name__.split(".")[-1], "trace": None})


@pytest.mark.parametrize(
    "a, b, want",
    [
        (A, B, 99.9),
        # a hook that provides on_qos_publish: every delivery on its own
        (A, {**B, "qos_runs": 25, "qos_run_entries": 1000}, 0.0),
        ({}, {}, None),
        ({"qos_begins": 1000}, {"qos_begins": 41000}, None),
        (A, A, None),
    ],
    ids=["broadcast", "hooked", "parent", "no_run_count", "qos0"],
)
def test_run_share(monkeypatch, a, b, want):
    monkeypatch.setattr(program_spans, "load", lambda: Slice(a, b))
    got = read(qos_run_share)
    assert got == want if want is None else got == pytest.approx(want)


@pytest.mark.parametrize(
    "a, b, want",
    [
        (A, B, 40.0),
        (A, {**B, "qos_runs": 25 + 64, "qos_run_entries": 1000 + 192}, 192 / 64),
        ({}, {}, None),
        ({"qos_runs": 1}, {"qos_runs": 2}, None),
        # no run settled: nothing, never a division by zero
        (A, {**B, "qos_runs": 25, "qos_run_entries": 1000}, None),
    ],
    ids=["broadcast", "shared", "parent", "no_entry_count", "no_run"],
)
def test_entries_a_run(monkeypatch, a, b, want):
    monkeypatch.setattr(program_spans, "load", lambda: Slice(a, b))
    got = read(qos_entries_per_run)
    assert got == want if want is None else got == pytest.approx(want)


@pytest.mark.parametrize("reader", [qos_run_share, qos_entries_per_run])
def test_no_slice_reads_nothing(monkeypatch, reader):
    monkeypatch.setattr(program_spans, "load", lambda: None)
    assert read(reader) is None
