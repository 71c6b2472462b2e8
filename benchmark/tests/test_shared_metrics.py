"""The three readers ``shared-100k.qos1`` brought, over slices made by
hand: what each divides by what, and nothing (None, never 0) from a
program whose snapshots lack the counts (a parent commit) or from a slice
in which no group was selected."""

import pytest

import program_spans
from layer_metrics import (
    share_offline_pick_share,
    share_select_us_per_group,
    view_materialize_share,
)


class Slice:
    def __init__(self, a, b):
        self.a, self.b, self.batches = a, b, []


# between the snapshots 20,000 publishes were fanned out, 22,000 groups
# selected in 44 ms, 7,000 of them on an offline member, and 19,800 lazy
# results forced into dicts
A = {"share_groups": 1000, "share_picks_offline": 300, "share_select_ns": 5_000_000,
     "view_materializations": 900, "fanout_n": 100}
B = {"share_groups": 23000, "share_picks_offline": 7300, "share_select_ns": 49_000_000,
     "view_materializations": 20700, "fanout_n": 20100}


def without(snapshot, *keys):
    return {k: v for k, v in snapshot.items() if k not in keys}


@pytest.mark.parametrize(
    "reader, metric, a, b, want",
    [
        (share_select_us_per_group, "share_select_us_per_group", A, B, 2.0),
        (share_offline_pick_share, "share_offline_pick_share", A, B, 31.818181818),
        (view_materialize_share, "view_materialize_share", A, B, 99.0),
        # a parent commit's snapshots hold no such count: nothing, not 0
        (share_select_us_per_group, "share_select_us_per_group",
         without(A, "share_select_ns"), without(B, "share_select_ns"), None),
        (share_select_us_per_group, "share_select_us_per_group",
         without(A, "share_groups"), without(B, "share_groups"), None),
        (share_offline_pick_share, "share_offline_pick_share",
         without(A, "share_picks_offline"), without(B, "share_picks_offline"), None),
        (view_materialize_share, "view_materialize_share",
         without(A, "view_materializations"), without(B, "view_materializations"), None),
        # a cell with no shared row: no group selected, nothing
        (share_select_us_per_group, "share_select_us_per_group",
         A, {**B, "share_groups": 1000, "share_select_ns": 5_000_000}, None),
        (share_offline_pick_share, "share_offline_pick_share",
         A, {**B, "share_groups": 1000, "share_picks_offline": 300}, None),
        # nothing fanned out between the snapshots: nothing
        (view_materialize_share, "view_materialize_share", A, A, None),
        # groups selected, every pick live; publishes with no group: 0
        (share_offline_pick_share, "share_offline_pick_share",
         A, {**B, "share_picks_offline": 300}, 0.0),
        (view_materialize_share, "view_materialize_share",
         A, {**B, "view_materializations": 900}, 0.0),
    ],
    ids=[
        "select_us", "offline_share", "materialize_share",
        "parent_select_ns", "parent_groups", "parent_offline", "parent_views",
        "no_group_select", "no_group_offline", "no_fanout",
        "every_pick_live", "no_view_forced",
    ],
)
def test_what_each_reader_reads(monkeypatch, reader, metric, a, b, want):
    monkeypatch.setattr(program_spans, "load", lambda: Slice(a, b))
    got = reader.read({"metric": metric, "trace": None})
    assert got == want if want is None else got == pytest.approx(want)


@pytest.mark.parametrize(
    "reader",
    [share_select_us_per_group, share_offline_pick_share, view_materialize_share],
    ids=lambda r: r.__name__.rpartition(".")[2],
)
def test_no_slice_reads_nothing(monkeypatch, reader):
    monkeypatch.setattr(program_spans, "load", lambda: None)
    name = reader.__name__.rpartition(".")[2]
    assert reader.read({"metric": name, "trace": None}) is None
