"""``ingest_run_share``'s reader over slices made by hand: the share of
the publishes taken in that an ingest run took, and nothing (None, never
0) from a program whose snapshots lack the counts: a parent commit."""

import pytest

import program_spans
from layer_metrics import ingest_run_share


class Slice:
    def __init__(self, a, b):
        self.a, self.b, self.batches = a, b, []


def read(monkeypatch, sl):
    monkeypatch.setattr(program_spans, "load", lambda: sl)
    return ingest_run_share.read({"metric": "ingest_run_share", "trace": None})


@pytest.mark.parametrize(
    "a, b, want",
    [
        # 950 of the 1,000 publishes between the snapshots came by the run
        ({"ingest_n": 100, "ingest_run_publishes": 4000, "ingest_runs": 90},
         {"ingest_n": 1100, "ingest_run_publishes": 4950, "ingest_runs": 120}, 95.0),
        # runs of one: all of them
        ({"ingest_n": 0, "ingest_run_publishes": 7, "ingest_runs": 7},
         {"ingest_n": 12, "ingest_run_publishes": 19, "ingest_runs": 19}, 100.0),
        # a gate that stayed shut is a reading: 0
        ({"ingest_n": 0, "ingest_run_publishes": 0}, {"ingest_n": 50, "ingest_run_publishes": 0}, 0.0),
        # the parent's snapshots hold no such count: nothing, not 0
        ({"ingest_n": 0}, {"ingest_n": 50}, None),
        # no publish was taken in between the snapshots: nothing
        ({"ingest_n": 5, "ingest_run_publishes": 5}, {"ingest_n": 5, "ingest_run_publishes": 5}, None),
    ],
    ids=["share", "runs_of_one", "gate_shut", "parent", "no_publish"],
)
def test_the_share_of_publishes_taken_in_by_the_run(monkeypatch, a, b, want):
    got = read(monkeypatch, Slice(a, b))
    assert got == want if want is None else got == pytest.approx(want)


def test_no_slice_reads_nothing(monkeypatch):
    assert read(monkeypatch, None) is None
