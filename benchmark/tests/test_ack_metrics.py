"""The two readers the ack run brought, over slices made by hand: the
quotient where the counts are there, and nothing (None, never 0) from a
program whose snapshots lack them: a parent commit, or one with the span
and without the run."""

import pytest

import program_spans
from layer_metrics import ack_run_share, loop_us_per_ack


class Slice:
    def __init__(self, a, b):
        self.a, self.b, self.batches = a, b, []


# 80,000 PUBACK frames read between the snapshots in scans without a
# publish, 0.2 s of frame loops; the runs took 79,000 of them
A = {"ack_n": 1000, "ack_busy_ns": 10**8, "ack_run_acks": 3000, "ack_runs": 90}
B = {"ack_n": 81000, "ack_busy_ns": 3 * 10**8, "ack_run_acks": 82000, "ack_runs": 2100}


def without(snapshot, *keys):
    return {k: v for k, v in snapshot.items() if k not in keys}


@pytest.mark.parametrize(
    "reader, a, b, want",
    [
        (ack_run_share, A, B, 98.75),
        (loop_us_per_ack, A, B, 2.5),
        # a parent commit: neither the span nor the run
        (ack_run_share, {}, {}, None),
        (loop_us_per_ack, {}, {}, None),
        # a parent with the span laid over it: the cost, no share
        (ack_run_share, without(A, "ack_run_acks", "ack_runs"),
         without(B, "ack_run_acks", "ack_runs"), None),
        (loop_us_per_ack, without(A, "ack_run_acks", "ack_runs"),
         without(B, "ack_run_acks", "ack_runs"), 2.5),
        # the run without the span: no frames to divide by
        (ack_run_share, without(A, "ack_n"), without(B, "ack_n"), None),
        # no PUBACK came in between the snapshots: nothing
        (ack_run_share, A, A, None),
        (loop_us_per_ack, A, A, None),
        # every frame went a frame at a time (a hook shut the gate): 0
        (ack_run_share, A, {**B, "ack_run_acks": 3000}, 0.0),
    ],
    ids=[
        "ack_run_share", "loop_us_per_ack", "parent_share", "parent_us",
        "span_alone_share", "span_alone_us", "run_without_span", "no_ack_share",
        "no_ack_us", "gate_shut",
    ],
)
def test_what_each_reader_reads(monkeypatch, reader, a, b, want):
    monkeypatch.setattr(program_spans, "load", lambda: Slice(a, b))
    got = reader.read({"metric": reader.__name__.rpartition(".")[2], "trace": None})
    assert got == want if want is None else got == pytest.approx(want)


@pytest.mark.parametrize(
    "reader", [ack_run_share, loop_us_per_ack],
    ids=lambda r: r.__name__.rpartition(".")[2],
)
def test_no_slice_reads_nothing(monkeypatch, reader):
    monkeypatch.setattr(program_spans, "load", lambda: None)
    assert reader.read({"metric": "x", "trace": None}) is None
