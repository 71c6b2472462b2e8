"""``share_early_select_share`` over slices made by hand: the groups a
completion slice picked in its prologue over all groups selected, and
nothing (None, never 0) from a program whose snapshots lack the counts
(a parent commit) or from a slice in which no group was selected."""

import pytest

import program_spans
from layer_metrics import share_early_select_share


class Slice:
    def __init__(self, a, b):
        self.a, self.b, self.batches = a, b, []


# between the snapshots 22,000 groups were selected, all in a prologue
A = {"share_groups": 1000, "share_groups_early": 1000}
B = {"share_groups": 23000, "share_groups_early": 23000}


@pytest.mark.parametrize(
    "a, b, want",
    [
        (A, B, 100.0),
        # a selecting hook: every pick waited for its publish's turn
        (A, {**B, "share_groups_early": 1000}, 0.0),
        (A, {**B, "share_groups_early": 6500}, 25.0),
        # a parent commit's snapshots hold no early count: nothing
        ({"share_groups": 1000}, {"share_groups": 23000}, None),
        ({}, {}, None),
        # a cell with no shared row: no group selected, nothing
        (A, A, None),
    ],
    ids=["all_early", "hooked", "quarter", "parent", "no_counts", "no_group"],
)
def test_what_the_reader_reads(monkeypatch, a, b, want):
    monkeypatch.setattr(program_spans, "load", lambda: Slice(a, b))
    got = share_early_select_share.read(
        {"metric": "share_early_select_share", "trace": None}
    )
    assert got == want if want is None else got == pytest.approx(want)


def test_no_slice_reads_nothing(monkeypatch):
    monkeypatch.setattr(program_spans, "load", lambda: None)
    assert share_early_select_share.read(
        {"metric": "share_early_select_share", "trace": None}
    ) is None
